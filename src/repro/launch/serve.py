"""Serving driver: wave-batched baseline + the continuous-batching engine.

:class:`BatchedServer` is the historical wave-barrier loop kept as the
serving baseline (and the benchmark's reference point): requests are packed
into waves, every slot decodes until the whole wave finishes, then the next
wave is admitted.  It now runs on the slot-cache path — each slot owns its
own sequence length — which fixes the old shared-``cache["len"]`` bug
(mixed prompt lengths in one wave conflated slot positions, so decode read
stale cache rows; tests/test_serve.py keeps the regression covered).

The production path is :class:`repro.serve.ServeEngine` (continuous
admission, bucketed prefill, no wave barrier — DESIGN.md §12):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 12 --max-new 16 --engine

Without ``--reduced`` the arch runs at its published widths.  The
resilient deployment is the engine behind :class:`repro.serve.ReplicaRouter`
(replicated dispatch with health checks, failover, load shedding and
hedging — DESIGN.md §14):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 12 --max-new 16 --router --replicas 2

Both the engine and the router take ``--paged`` (with ``--block-size``/
``--blocks``) to admit on free KV-cache pool blocks instead of
worst-case dense slots (DESIGN.md §15) — the capacity win on long-tail
prompt mixes.
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serve.engine import EngineConfig, ServeEngine, ServeRequest

# re-export: Request predates ServeRequest and external callers import it
# from here
Request = ServeRequest


class BatchedServer:
    """Wave-barrier batching over the slot-cache (prefill, decode) path.

    Admission happens only between waves (the historical behaviour, kept
    as the baseline the continuous engine is benchmarked against), but
    slot state is correct: per-slot lengths, per-slot masking — a wave may
    mix prompt lengths freely."""

    def __init__(self, bundle, params, *, slots: int = 4,
                 cache_len: int = 256, seed: int = 0):
        if bundle.decode_slotted is None:
            raise ValueError(f"family {bundle.cfg.family!r} has no slotted "
                             f"serving path")
        self.bundle = bundle
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.active: List[Optional[ServeRequest]] = [None] * slots
        self.cache = bundle.make_slot_cache(slots, cache_len)
        self._decode = jax.jit(lambda p, c, t, a: bundle.decode_slotted(
            p, c, {"tokens": t, "active": a}))
        self._prefill = jax.jit(lambda p, t, l: bundle.prefill_slotted(
            p, {"tokens": t, "lens": l, "cache_len": cache_len}))
        self._specs = {k: v for k, v in bundle.cache_specs().items()
                       if k != "len"}

    def _prefill_slot(self, slot: int, req: ServeRequest):
        """Prefill one request (batch 1 — the baseline keeps the historical
        slot-by-slot admission) and splice its cache rows into the slot."""
        toks = jnp.asarray(req.prompt, jnp.int32)[None]
        lens = jnp.asarray([len(req.prompt)], jnp.int32)
        logits, cache1 = self._prefill(self.params, toks, lens)
        idx = jnp.asarray([slot])
        cache = dict(self.cache)
        for key, spec in self._specs.items():
            ax = spec.index("batch")
            sl = (slice(None),) * ax + (idx,)
            cache[key] = cache[key].at[sl].set(cache1[key])
        cache["lens"] = cache["lens"].at[idx].set(cache1["lens"])
        self.cache = cache
        req.out.append(int(jnp.argmax(logits[0])))

    def run(self, requests: List[ServeRequest], log=print
            ) -> List[ServeRequest]:
        pending = list(requests)
        finished: List[ServeRequest] = []
        round_no = 0
        last_tok = np.zeros((self.slots,), np.int32)
        while pending or any(self.active):
            # fill free slots with a fresh wave (barrier: only between waves)
            wave = []
            for s in range(self.slots):
                if self.active[s] is None and pending:
                    req = pending.pop(0)
                    self.active[s] = req
                    wave.append((s, req))
            for s, req in wave:
                self._prefill_slot(s, req)
                last_tok[s] = req.out[-1]
            # decode until every active request finished its budget
            while any(r is not None and not r.done for r in self.active):
                act = np.array([r is not None and not r.done
                                for r in self.active])
                logits, self.cache = self._decode(
                    self.params, self.cache,
                    jnp.asarray(last_tok[:, None]), jnp.asarray(act))
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
                lens = np.asarray(self.cache["lens"])
                for s, r in enumerate(self.active):
                    if r is None or r.done:
                        continue
                    r.out.append(int(nxt[s]))
                    last_tok[s] = nxt[s]
                    if len(r.out) >= r.max_new or \
                            int(lens[s]) >= self.cache_len:
                        r.done = True
            for s, r in enumerate(self.active):
                if r is not None and r.done:
                    finished.append(r)
                    self.active[s] = None
            round_no += 1
            log(f"[serve] round {round_no}: finished={len(finished)} "
                f"pending={len(pending)}")
        return finished


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=ALL_ARCHS)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=64,
                    help="per-slot KV-cache capacity in tokens")
    ap.add_argument("--engine", action="store_true",
                    help="use the continuous-batching ServeEngine instead "
                         "of the wave-barrier baseline")
    ap.add_argument("--router", action="store_true",
                    help="front ServeEngine replicas with the ReplicaRouter "
                         "(health checks, failover, shedding, hedging)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="replica count for --router (device-affine across "
                         "jax.devices() when more than one is present)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: admit on free pool blocks instead "
                         "of worst-case dense slots (DESIGN.md §15); applies "
                         "to --engine and --router")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV-cache block for --paged")
    ap.add_argument("--blocks", type=int, default=None,
                    help="pool size in blocks for --paged (default: worst "
                         "case, slots * cache_len / block_size)")
    args = ap.parse_args(argv)
    if args.paged and not (args.engine or args.router):
        ap.error("--paged needs --engine or --router (the wave-barrier "
                 "baseline is dense-only)")
    enable_compile_cache()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(rid=i,
                         prompt=rng.integers(0, cfg.vocab_size, 12).astype(
                             np.int32),
                         max_new=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    ecfg = EngineConfig(slots=args.slots, cache_len=args.cache_len,
                        pad_to=8 if bundle.prefill_pads else 1,
                        paged=args.paged, block_size=args.block_size,
                        n_blocks=args.blocks)
    if args.router:
        from repro.serve.router import ReplicaRouter, RouterConfig
        devices = jax.devices()
        router = ReplicaRouter(bundle, params, RouterConfig(
            replicas=args.replicas, engine=ecfg),
            devices=devices if len(devices) > 1 else None)
        done = router.run(reqs)
        print(f"router stats: {router.stats}")
    elif args.engine:
        engine = ServeEngine(bundle, params, ecfg)
        done = engine.run(reqs)
        print(f"engine stats: {engine.stats()}")
    else:
        server = BatchedServer(bundle, params, slots=args.slots,
                               cache_len=args.cache_len)
        done = server.run(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens/dt:.1f} tok/s)")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out[:8]}...")


if __name__ == "__main__":
    main()
