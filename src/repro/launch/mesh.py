"""Production mesh construction + per-arch sharding-rule overrides.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run must set XLA_FLAGS before jax initializes.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
from jax.sharding import AxisType

from repro.distributed.sharding import Physical, default_rules


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis Auto.  The sharding rules place
    arrays by ``with_sharding_constraint`` under the mesh, which JAX's
    default Explicit axes refuse ("can only refer to Auto axes")."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def local_search_devices(max_devices: Optional[int] = None) -> List:
    """The accelerators the search orchestrator may shard signature buckets
    across (DESIGN.md §11) — one scheduler worker group per entry.

    A FUNCTION for the same reason as :func:`make_production_mesh`: calling
    it initializes the jax backend, so it must only run after any
    ``XLA_FLAGS`` staging (``--xla_force_host_platform_device_count=N``
    simulates an N-device host for tests/benchmarks).
    """
    devs = list(jax.local_devices())
    return devs[:max_devices] if max_devices else devs


# Divisibility-driven deviations from the defaults (DESIGN.md §5):
# * whisper-tiny / mamba2-780m: vocab (51865 / 50280) is not divisible by the
#   16-way model axis.  Sharding the embedding's d_model axis instead trips
#   an XLA SPMD gather bug under the microbatch loop ("Slice dim size 1536
#   greater than dynamic slice dimension: 96"), so these small tables
#   (<= 160 MB bf16) are simply replicated.
ARCH_RULE_OVERRIDES: Dict[str, Dict[str, Physical]] = {
    "whisper-tiny": {"vocab": None, "embed_unsharded": None},
    "mamba2-780m": {"vocab": None, "embed_unsharded": None},
}


def rules_for(arch: str, *, multi_pod: bool, global_batch: int,
              overrides: Optional[Dict[str, Physical]] = None
              ) -> Dict[str, Physical]:
    rules = default_rules(multi_pod)
    rules.update(ARCH_RULE_OVERRIDES.get(arch, {}))
    if global_batch == 1:
        rules["batch"] = None   # degenerate long-context cells
    if overrides:
        rules.update(overrides)
    return rules
