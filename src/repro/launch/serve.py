"""Serving driver: the continuous-batching engine, alone or behind the router.

By default the requests run through one :class:`repro.serve.ServeEngine`
(continuous admission, bucketed prefill, per-slot cache lengths —
DESIGN.md §12):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 12 --max-new 16

Without ``--reduced`` the arch runs at its published widths.  The
resilient deployment is the engine behind :class:`repro.serve.ReplicaRouter`
(replicated dispatch with health checks, failover, load shedding and
hedging — DESIGN.md §14):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
      --requests 12 --max-new 16 --router --replicas 2

Both take ``--paged`` (with ``--block-size``/``--blocks``) to admit on
free KV-cache pool blocks instead of worst-case dense slots (DESIGN.md
§15) — the capacity win on long-tail prompt mixes.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ALL_ARCHS, get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serve.engine import EngineConfig, ServeEngine, ServeRequest


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
    ap.add_argument("--router", action="store_true",
                    help="front ServeEngine replicas with the ReplicaRouter "
                         "(health checks, failover, shedding, hedging)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="replica count for --router (device-affine across "
                         "jax.devices() when more than one is present)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: admit on free pool blocks instead "
                         "of worst-case dense slots (DESIGN.md §15)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV-cache block for --paged")
    ap.add_argument("--blocks", type=int, default=None,
                    help="pool size in blocks for --paged (default: worst "
                         "case, slots * cache_len / block_size)")
    args = ap.parse_args(argv)
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
    else:
        engine = ServeEngine(bundle, params, ecfg)
        done = engine.run(reqs)
        print(f"engine stats: {engine.stats()}")
    dt = time.time() - t0
    total_tokens = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens/dt:.1f} tok/s)")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out[:8]}...")


if __name__ == "__main__":
    main()
