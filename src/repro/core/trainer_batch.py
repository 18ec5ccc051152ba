"""Bucketed vmap-stacked candidate training (DESIGN.md §9).

The expensive-objective stage trains every surviving child to measure
detection / false-alarm rates.  Candidates are tiny 1D-CNNs, so a scalar
`train_candidate` loop is dominated by per-step dispatch overhead, not
compute.  This module amortizes that overhead: children are bucketed by
*shape signature* — the static tuple that determines a compiled jaxpr — and
each bucket's per-candidate parameters are stacked into leading-axis pytrees
so the whole bucket trains inside ONE `jax.vmap`-ed, `lax.scan`-stepped XLA
dispatch sharing a single on-device dataset.

Parity contract: per-candidate results match the scalar
:func:`~repro.core.trainer.train_candidate` under matched seeds.  The pieces
that guarantee it:

* init vmaps :func:`~repro.core.trainer.init_candidate` over the same
  per-candidate PRNG keys (threefry is deterministic, vmapped or not);
* minibatch/calibration indices come from the shared
  :func:`~repro.core.trainer.presample_indices` stream, transferred once
  (no per-step host→device copies);
* the scan body IS :func:`~repro.core.trainer.train_step_pure`, the same
  traceable step the scalar path jits;
* quantization bit widths ride along as stacked per-candidate *data* (not
  part of the signature): :func:`~repro.hwlib.quant.fake_quant` is
  vmap-clean for traced bits, so candidates differing only in precision
  share one bucket and one compiled program.

Singleton buckets fall back to the scalar path (vmap over one candidate
buys nothing and would double-compile); they train on the same staged
dataset.

Device affinity (DESIGN.md §11): pass ``device=`` and the bucket's staged
dataset, stacked index/key/bit arrays and eval batches are committed to
that accelerator with ``jax.device_put`` — different signature buckets of
one generation then train concurrently on different devices.  The staging
cache is keyed per ``(input_length, device)`` and the compile cache per
``(signature, steps, batch, lr, device)``, so device-affine dispatch never
thrashes either.  Numerics are device-independent: the same compiled
program runs wherever the data lives, so results are bit-identical across
devices (asserted in tests/test_multi_device.py).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.genome import Genome
from repro.core.search_space import DEFAULT_SPACE, SearchSpace
from repro.core.trainer import (
    TrainResult,
    detection_rates,
    forward,
    init_candidate,
    prep_inputs,
    presample_indices,
    refresh_bn_pure,
    train_candidate,
    train_step_pure,
)
from repro.hwlib.layers import LayerSpec
from repro.hwlib.quant import QuantConfig
from repro.optim import adamw

ShapeSignature = Tuple[Tuple[Tuple, ...], int, bool]


def shape_signature(genome: Genome, space: SearchSpace = DEFAULT_SPACE,
                    use_quant: bool = True) -> ShapeSignature:
    """The static tuple that determines a candidate's compiled jaxpr:
    per-layer kernel signatures (kind, channels, kernel, stride, BN), the
    input length (decimation gene) and whether fake-quant is traced at all.

    Quantization *bit widths* are deliberately absent: they enter the
    batched trainer as stacked per-candidate data, so genomes that differ
    only in precision hash to the same signature and train in one bucket.
    """
    specs = genome.phenotype(space)
    return (tuple(s.signature() for s in specs),
            genome.input_length(space),
            bool(use_quant))


def bucket_by_signature(genomes: Sequence[Genome],
                        space: SearchSpace = DEFAULT_SPACE,
                        use_quant: bool = True
                        ) -> Dict[ShapeSignature, List[int]]:
    """Group candidate indices by :func:`shape_signature` (insertion-ordered,
    so dispatch order is deterministic given the input order)."""
    buckets: Dict[ShapeSignature, List[int]] = {}
    for i, g in enumerate(genomes):
        buckets.setdefault(shape_signature(g, space, use_quant), []).append(i)
    return buckets


# ---------------------------------------------------------------------------
# Compile cache: one (train, eval) function pair per signature + hyperparams.
# jit re-specializes on the bucket's leading axis internally; this cache
# avoids re-tracing/rebuilding the python closures per generation.
# ---------------------------------------------------------------------------

_CACHE_LOCK = threading.Lock()
_BUCKET_FN_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_CACHE_STATS = {"hits": 0, "misses": 0}
_CACHE_MAX = 128  # LRU-evicted: long-lived processes must not pin every
#                   signature's jitted executables forever


def compile_cache_stats() -> Dict[str, int]:
    with _CACHE_LOCK:
        return {**_CACHE_STATS, "size": len(_BUCKET_FN_CACHE)}


def reset_compile_cache() -> None:
    with _CACHE_LOCK:
        _BUCKET_FN_CACHE.clear()
        _CACHE_STATS.update(hits=0, misses=0)


def _build_bucket_fns(specs: Sequence[LayerSpec], use_quant: bool,
                      opt) -> tuple:
    """(train_bucket, eval_bucket) for one signature.

    ``train_bucket(keys, idx, calib_idx, bits, x_tr, y_tr)`` runs the whole
    bucket's training — init, `steps` scanned SGD steps, BN re-estimation —
    in one dispatch and returns the stacked trained params.
    ``eval_bucket(params, bits, xb, yb)`` forwards one shared eval batch
    through every candidate, returning per-candidate NLL sums and argmax
    predictions (device-resident; the caller accumulates).
    """

    def _quant(bits):
        if not use_quant:
            return None
        return QuantConfig(weight_bits=bits[0], act_bits=bits[1],
                           input_bits=bits[2])

    def _train_one(key, idx, calib_idx, bits, x_tr, y_tr):
        quant = _quant(bits)
        params = init_candidate(key, specs)
        opt_state = opt.init(params)

        def body(carry, idx_row):
            params, opt_state = carry
            params, opt_state, loss = train_step_pure(
                params, opt_state, x_tr[idx_row], y_tr[idx_row],
                specs=specs, quant=quant, opt=opt)
            return (params, opt_state), loss

        (params, _), _ = jax.lax.scan(body, (params, opt_state), idx)
        return refresh_bn_pure(params, specs, x_tr[calib_idx], quant)

    train_bucket = jax.jit(jax.vmap(_train_one,
                                    in_axes=(0, 0, 0, 0, None, None)))

    def _eval_one(params, bits, xb, yb):
        logits = forward(params, specs, xb, _quant(bits), train=False)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, yb[:, None], axis=1).sum()
        return nll, jnp.argmax(logits, axis=-1)

    eval_bucket = jax.jit(jax.vmap(_eval_one, in_axes=(0, 0, None, None)))
    return train_bucket, eval_bucket


def _bucket_fns(sig: ShapeSignature, specs: Sequence[LayerSpec],
                steps: int, batch_size: int, lr: float,
                device=None) -> tuple:
    key = (sig, steps, batch_size, float(lr), device)
    with _CACHE_LOCK:
        fns = _BUCKET_FN_CACHE.get(key)
        if fns is not None:
            _CACHE_STATS["hits"] += 1
            _BUCKET_FN_CACHE.move_to_end(key)
            return fns
        _CACHE_STATS["misses"] += 1
    opt = adamw(lr, b1=0.9, b2=0.99, weight_decay=1e-4)
    fns = _build_bucket_fns(specs, use_quant=sig[2], opt=opt)
    with _CACHE_LOCK:
        # lost a build race: keep the first pair so its jit cache wins
        fns = _BUCKET_FN_CACHE.setdefault(key, fns)
        _BUCKET_FN_CACHE.move_to_end(key)
        while len(_BUCKET_FN_CACHE) > _CACHE_MAX:
            _BUCKET_FN_CACHE.popitem(last=False)
    return fns


# ---------------------------------------------------------------------------
# Bucket training
# ---------------------------------------------------------------------------

def _put(x, device=None) -> jnp.ndarray:
    """Commit ``x`` to ``device`` (default device when None).  device_put
    with an explicit device yields a *committed* array, so every jit that
    consumes it compiles for and executes on that accelerator."""
    return jnp.asarray(x) if device is None else jax.device_put(x, device)


def _train_bucket(genomes: List[Genome], seeds: Sequence[int],
                  sig: ShapeSignature, space: SearchSpace,
                  x_tr: jnp.ndarray, y_tr: jnp.ndarray,
                  x_va: np.ndarray, y_va: np.ndarray,
                  steps: int, batch_size: int, lr: float,
                  eval_batch: int, device=None) -> List[TrainResult]:
    specs = genomes[0].phenotype(space)
    train_bucket, eval_bucket = _bucket_fns(sig, specs, steps, batch_size,
                                            lr, device)

    n = int(x_tr.shape[0])
    idx_rows, calib_rows = zip(*(presample_indices(s, n, steps, batch_size)
                                 for s in seeds))
    idx = _put(np.stack(idx_rows), device)       # (N, steps, B)
    calib = _put(np.stack(calib_rows), device)   # (N, C)
    keys = _put(np.stack([np.asarray(jax.random.PRNGKey(s))
                          for s in seeds]), device)
    if sig[2]:
        bits = _put(np.stack(
            [(q.weight_bits, q.act_bits, q.input_bits)
             for q in (g.quant(space) for g in genomes)]).astype(np.int32),
            device)
    else:
        bits = _put(np.zeros((len(genomes), 3), np.int32), device)

    params = train_bucket(keys, idx, calib, bits, x_tr, y_tr)

    # chunked eval mirrors the scalar `evaluate` exactly (the input
    # fake-quant scale is a per-chunk max, so chunk boundaries are part of
    # the numerics contract); accumulation stays on device until the end.
    nll_parts, preds = [], []
    for i in range(0, len(x_va), eval_batch):
        nll, pred = eval_bucket(params, bits,
                                _put(x_va[i:i + eval_batch], device),
                                _put(y_va[i:i + eval_batch], device))
        nll_parts.append(nll)
        preds.append(pred)
    pred = np.asarray(jnp.concatenate(preds, axis=1))       # (N, n_va)
    nll = np.asarray(jnp.sum(jnp.stack(nll_parts), axis=0))  # (N,)

    out = []
    for k in range(len(genomes)):
        det, fa = detection_rates(pred[k], y_va)
        vl = float(nll[k]) / len(y_va)
        if not np.isfinite(vl):
            # per-candidate quarantine (DESIGN.md §13): one diverged
            # candidate (NaN/inf loss poisons its NLL) must not fail the
            # whole vmap bucket — it alone reports pessimistic rates (its
            # argmax predictions are garbage) while its bucket-mates keep
            # their real results.  The non-finite val_loss rides along so
            # the search driver maps it to the schema-pessimistic row.
            det, fa = 0.0, 1.0
        out.append(TrainResult(detection_rate=det, false_alarm_rate=fa,
                               val_loss=vl, steps=steps))
    return out


def train_candidates_batched(
    genomes: Sequence[Genome],
    data_train: Tuple[np.ndarray, np.ndarray],
    data_val: Tuple[np.ndarray, np.ndarray],
    *,
    space: SearchSpace = DEFAULT_SPACE,
    steps: int = 300,
    batch_size: int = 64,
    lr: float = 3e-3,
    seed: int = 0,
    seeds: Optional[Sequence[int]] = None,
    use_quant: bool = True,
    eval_batch: int = 256,
    min_bucket: int = 2,
    stage_cache: Optional[Dict[tuple, tuple]] = None,
    device=None,
) -> List[TrainResult]:
    """Train a whole child generation, bucketed by shape signature.

    Returns one :class:`TrainResult` per input genome, in input order.
    ``seeds`` optionally gives per-candidate training seeds (default: the
    single ``seed`` for all, matching the search driver's scalar behavior).
    Buckets smaller than ``min_bucket`` take the scalar
    :func:`train_candidate` path.  ``stage_cache`` ((want_len, device) →
    staged arrays) lets a long-lived caller keep the prepped dataset
    resident on device across calls — the search driver passes one per
    search, so concurrently dispatched buckets don't re-upload the
    training set.  ``device`` pins every bucket of this call to one
    accelerator (the device-affine scheduler passes its worker's device);
    ``None`` keeps today's default-device behavior.
    """
    genomes = list(genomes)
    if seeds is None:
        seeds = [seed] * len(genomes)
    elif len(seeds) != len(genomes):
        raise ValueError("seeds must align with genomes")
    results: List[Optional[TrainResult]] = [None] * len(genomes)

    staged = stage_cache if stage_cache is not None else {}

    def stage(want_len: int) -> tuple:
        got = staged.get((want_len, device))
        if got is None:  # setdefault: concurrent stagers agree on one copy
            got = staged.setdefault((want_len, device), (
                _put(prep_inputs(data_train[0], want_len), device),
                _put(data_train[1], device),
                prep_inputs(data_val[0], want_len),
                data_val[1]))
        return got

    for sig, rows in bucket_by_signature(genomes, space, use_quant).items():
        x_tr, y_tr, x_va, y_va = stage(sig[1])
        if len(rows) < min_bucket:
            for i in rows:
                with jax.default_device(device):
                    # already at the genome's length: prep_inputs and
                    # jnp.asarray pass the staged arrays through
                    results[i] = train_candidate(
                        genomes[i], (x_tr, y_tr), (x_va, y_va), space=space,
                        steps=steps, batch_size=batch_size, lr=lr,
                        seed=seeds[i], use_quant=use_quant)
            continue
        bucket_results = _train_bucket(
            [genomes[i] for i in rows], [seeds[i] for i in rows], sig,
            space, x_tr, y_tr, x_va, y_va, steps, batch_size, lr,
            eval_batch, device)
        for i, r in zip(rows, bucket_results):
            results[i] = r
    return results  # type: ignore[return-value]
