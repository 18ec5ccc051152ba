"""Continuous-batching inference serving (DESIGN.md §12, §14).

* :class:`~repro.serve.engine.ServeEngine` — slot-cache continuous
  batching over a ModelBundle's slotted prefill/decode path.
* :class:`~repro.serve.router.ReplicaRouter` — N engine replicas behind
  one submit/run/drain API: health-checked dispatch, failover, load
  shedding, hedged requests.
* :mod:`repro.serve.loadgen` — open-loop Poisson / heavy-tail / burst /
  long-tail-prompt workloads.
* :mod:`repro.serve.paged` — :class:`~repro.serve.paged.BlockPool`
  block-granular KV-cache allocator behind ``EngineConfig(paged=True)``
  (DESIGN.md §15).
* :func:`~repro.serve.winner.serve_winner` — genome front-end: NAS winner
  → train → compile → serve (search → implement → deploy);
  :func:`~repro.serve.winner.replicate_winner` adds replicated dispatch.
"""
from repro.serve.buckets import PrefillBucket, build_buckets
from repro.serve.engine import (
    EngineConfig,
    ServeEngine,
    ServeRequest,
    greedy_reference,
)
from repro.serve.loadgen import (
    gamma_workload,
    longtail_workload,
    onoff_workload,
    poisson_workload,
)
from repro.serve.paged import BlockPool, blocks_for
from repro.serve.router import ReplicaRouter, RouterConfig
from repro.serve.winner import (
    ReplicatedWinner,
    ServableWinner,
    compile_winner,
    replicate_winner,
    serve_winner,
)

__all__ = [
    "BlockPool",
    "EngineConfig",
    "PrefillBucket",
    "ReplicaRouter",
    "ReplicatedWinner",
    "RouterConfig",
    "ServableWinner",
    "ServeEngine",
    "ServeRequest",
    "blocks_for",
    "build_buckets",
    "compile_winner",
    "gamma_workload",
    "greedy_reference",
    "longtail_workload",
    "onoff_workload",
    "poisson_workload",
    "replicate_winner",
    "serve_winner",
]
