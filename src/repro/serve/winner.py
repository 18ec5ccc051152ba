"""Genome front-end: search winner → deployable server (DESIGN.md §12).

Closes HALF's loop (search → implement → deploy): pick the best feasible
candidate for a design goal (`select_for_goal`), train it to convergence,
compile the deployment artifact (BN-folded + quantized params, unrolling
plan, accumulator formats — core/compile_model.py), and serve batched
classification requests through one jitted deployment-mode forward.

The ECG winners are single-forward classifiers, so "serving" is the
prefill-only degenerate case of the engine: admission buckets by batch
size (the input length is fixed by the genome's decimation gene), no
decode loop, no cache.

:class:`ReplicatedWinner` is the classification analogue of the serving
router (DESIGN.md §14): the compiled winner's params are staged onto N
devices with one jitted forward each, batches round-robin across live
replicas, a replica that raises fails over to the next one mid-call
(same batch, bit-identical logits — the forward is deterministic), and a
failure streak quarantines the replica with the scheduler's last-live
protection.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compile_model import CompiledModel, compile_candidate
from repro.core.faults import FaultPlan, InjectedCrash
from repro.core.genome import Genome, describe
from repro.core.objective_schema import DesignGoal
from repro.core.search_space import DEFAULT_SPACE, SearchSpace
from repro.core.trainer import prep_inputs, train_candidate
from repro.serve.buckets import pad_batch


@dataclasses.dataclass
class ServableWinner:
    """A compiled search winner plus its jitted deployment forward."""

    genome: Genome
    compiled: CompiledModel
    goal: Optional[str]
    input_length: int
    train_meta: Dict[str, float]
    _predict: Any = None           # jitted (B, L, 2) -> (B, n_classes)
    batches_served: int = 0
    paged: bool = False            # KV-cache preference recorded for the
    #   token-serving deployment path (launch/serve.py --paged);
    #   the classifier forward itself has no KV cache

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Deployment-mode logits for a batch of windows ``(B, L, 2)``.

        Inputs at the dataset's max resolution are decimated to the
        genome's input length; the batch is padded to a power of two so
        repeated serving hits a handful of compiled executables."""
        x = prep_inputs(np.asarray(x), self.input_length)
        b = x.shape[0]
        bp = pad_batch(b, max(b, 1))
        if bp != b:
            x = np.concatenate([x, np.zeros((bp - b,) + x.shape[1:],
                                            x.dtype)])
        logits = self._predict(jnp.asarray(x))
        self.batches_served += 1
        return np.asarray(logits[:b])

    def classify(self, x: np.ndarray) -> np.ndarray:
        return self.predict(x).argmax(axis=1)

    def report(self) -> str:
        lines = [f"goal={self.goal} input_length={self.input_length} "
                 f"det={self.train_meta['detection_rate']:.3f} "
                 f"fa={self.train_meta['false_alarm_rate']:.3f}"]
        lines.append(self.compiled.report())
        return "\n".join(lines)


class _WinnerReplica:
    """One staged copy of a compiled winner plus its health state."""

    def __init__(self, idx: int, predict: Any, device: Any):
        self.idx = idx
        self.predict = predict
        self.device = device
        self.live = True
        self.fail_streak = 0
        self.batches_served = 0


@dataclasses.dataclass
class ReplicatedWinner:
    """N device-affine copies of a :class:`ServableWinner` behind one
    ``predict``: round-robin dispatch over live replicas, mid-call
    failover on a raising replica (the jitted forward is deterministic,
    so the retried batch returns bit-identical logits), fail-streak
    quarantine with last-live protection (core/scheduler.py idiom)."""

    winner: ServableWinner
    replicas: List[_WinnerReplica]
    quarantine_after: int = 3
    faults: Optional[FaultPlan] = None  # "router.dispatch" inject point
    stats: Dict[str, Any] = dataclasses.field(default_factory=lambda: {
        "batches": 0, "failovers": 0, "quarantined": []})

    @property
    def input_length(self) -> int:
        return self.winner.input_length

    @property
    def live_replicas(self) -> List[int]:
        return [r.idx for r in self.replicas if r.live]

    def _fail(self, rep: _WinnerReplica) -> None:
        rep.fail_streak += 1
        others = [r for r in self.replicas if r.live and r is not rep]
        if rep.fail_streak >= self.quarantine_after and others:
            rep.live = False
            self.stats["quarantined"].append(rep.idx)

    def predict(self, x: np.ndarray) -> np.ndarray:
        """Deployment-mode logits for a batch ``(B, L, 2)``: dispatched to
        the next live replica (round-robin on batch count), failing over
        through the survivors when one raises.  Only when *every* live
        replica fails on the same batch does the error propagate."""
        x = prep_inputs(np.asarray(x), self.winner.input_length)
        b = x.shape[0]
        bp = pad_batch(b, max(b, 1))
        if bp != b:
            x = np.concatenate([x, np.zeros((bp - b,) + x.shape[1:],
                                            x.dtype)])
        xd = jnp.asarray(x)
        rid = self.stats["batches"]
        self.stats["batches"] += 1
        live = [r for r in self.replicas if r.live]
        order = live[rid % len(live):] + live[:rid % len(live)]
        last_err: Optional[BaseException] = None
        for i, rep in enumerate(order):
            if not rep.live:    # quarantined by an earlier lap's _fail
                continue
            try:
                if self.faults is not None:
                    spec = self.faults.check("router.dispatch", rid=rid,
                                             replica=rep.idx, tick=rid)
                    if spec is not None and spec.kind in ("crash",
                                                          "device_loss"):
                        raise InjectedCrash(
                            f"injected {spec.kind} at router.dispatch "
                            f"(replica {rep.idx})")
                logits = rep.predict(jnp.asarray(xd, copy=False)
                                     if rep.device is None
                                     else jax.device_put(xd, rep.device))
                rep.fail_streak = 0
                rep.batches_served += 1
                return np.asarray(logits[:b])
            except Exception as err:  # noqa: BLE001 — any replica failure
                last_err = err
                self._fail(rep)
                if i + 1 < len(order):
                    self.stats["failovers"] += 1
        raise RuntimeError(
            f"every live replica failed batch {rid}") from last_err

    def classify(self, x: np.ndarray) -> np.ndarray:
        return self.predict(x).argmax(axis=1)

    def report(self) -> str:
        live = sum(r.live for r in self.replicas)
        return (f"replicas={live}/{len(self.replicas)} live "
                f"(quarantined={self.stats['quarantined']})\n"
                + self.winner.report())


def replicate_winner(
    winner: ServableWinner,
    replicas: int = 2,
    *,
    devices: Optional[Sequence[Any]] = None,
    space: SearchSpace = DEFAULT_SPACE,
    quarantine_after: int = 3,
    faults: Optional[FaultPlan] = None,
) -> ReplicatedWinner:
    """Stage a compiled winner onto N replicas (device-affine when
    ``devices`` is given: replica i pins to ``devices[i % len]``) and
    front them with round-robin + failover dispatch.  Every replica runs
    the same deployment-mode forward on the same folded params, so
    replica choice never changes the logits."""
    from repro.core.trainer import forward

    if replicas < 1:
        raise ValueError("replicate_winner needs at least one replica")
    specs = winner.genome.phenotype(space)

    def _fwd(p, x):
        return forward(p, specs, x, quant=None, train=False)

    reps = []
    for i in range(replicas):
        dev = devices[i % len(devices)] if devices else None
        p = winner.compiled.params if dev is None \
            else jax.device_put(winner.compiled.params, dev)
        reps.append(_WinnerReplica(i, functools.partial(jax.jit(_fwd), p),
                                   dev))
    return ReplicatedWinner(winner=winner, replicas=reps,
                            quarantine_after=quarantine_after, faults=faults)


def compile_winner(
    genome: Genome,
    data_train: Tuple[np.ndarray, np.ndarray],
    data_val: Tuple[np.ndarray, np.ndarray],
    *,
    space: SearchSpace = DEFAULT_SPACE,
    goal: Optional[str] = None,
    train_steps: int = 300,
    train_batch: int = 64,
    seed: int = 0,
) -> ServableWinner:
    """Train + compile one genome into a :class:`ServableWinner`."""
    from repro.core.trainer import (evaluate, forward, init_candidate,
                                    presample_indices, refresh_bn_stats)
    from repro.optim import adamw
    from repro.core.trainer import make_train_step_indexed

    specs = genome.phenotype(space)
    quant = genome.quant(space)
    want_len = genome.input_length(space)
    x_tr = prep_inputs(data_train[0], want_len)

    rng = jax.random.PRNGKey(seed)
    params = init_candidate(rng, specs)
    opt = adamw(3e-3, b1=0.9, b2=0.99, weight_decay=1e-4)
    opt_state = opt.init(params)
    step_fn = make_train_step_indexed(specs, quant, opt)
    idx, calib_idx = presample_indices(seed, len(x_tr), train_steps,
                                       train_batch)
    x_dev, y_dev = jnp.asarray(x_tr), jnp.asarray(data_train[1])
    idx_dev = jnp.asarray(idx)
    for s in range(train_steps):
        params, opt_state, _ = step_fn(params, opt_state, x_dev, y_dev,
                                       idx_dev[s])
    params = refresh_bn_stats(params, specs, x_dev[jnp.asarray(calib_idx)],
                              quant)
    x_va = prep_inputs(data_val[0], want_len)
    det, fa, nll = evaluate(params, specs, quant, x_va, data_val[1])

    compiled = compile_candidate(genome, params, x_dev[jnp.asarray(calib_idx)],
                                 space=space)

    # one compiled deployment-mode executable (params are baked in as
    # constants — BN-folded and fake-quantized by compile_candidate)
    predict = jax.jit(lambda x: forward(compiled.params, specs, x,
                                        quant=None, train=False))
    return ServableWinner(
        genome=genome,
        compiled=compiled,
        goal=goal,
        input_length=want_len,
        train_meta={"detection_rate": det, "false_alarm_rate": fa,
                    "val_loss": nll, "steps": float(train_steps)},
        _predict=predict,
    )


def serve_winner(
    search,                         # EvolutionarySearch
    state,                          # NASState
    goal: Union[None, str, DesignGoal] = None,
    *,
    data_train: Tuple[np.ndarray, np.ndarray],
    data_val: Tuple[np.ndarray, np.ndarray],
    train_steps: int = 300,
    train_batch: int = 64,
    seed: int = 0,
    replicas: int = 1,
    devices: Optional[Sequence[Any]] = None,
    paged: bool = False,
    log=print,
) -> Union[ServableWinner, "ReplicatedWinner"]:
    """search → implement → deploy: pick the goal's best feasible
    candidate, train + compile it, return a serving handle.

    ``replicas > 1`` routes the winner through replicated dispatch
    (:func:`replicate_winner`): device-affine copies, round-robin +
    failover, fail-streak quarantine — the resilient deployment default.

    ``paged=True`` records the paged KV-cache preference on the handle
    for the token-serving deployment front-end (``launch/serve.py
    --paged`` builds ``EngineConfig(paged=True)`` — DESIGN.md §15); the
    winner's own classification forward is prefill-only and has no cache,
    so this changes nothing about ``predict``.

    Raises ``LookupError`` when no candidate meets the goal's constraints
    (serve nothing rather than an infeasible model)."""
    cand = search.select_for_goal(state, goal)
    if cand is None:
        raise LookupError(f"no feasible candidate for goal {goal!r} — "
                          f"run more generations")
    goal_name = goal if isinstance(goal, (str, type(None))) else goal.name
    log(f"[serve] winner for goal={goal_name}: "
        f"{describe(cand.genome, search.space)}")
    t0 = time.time()
    winner = compile_winner(cand.genome, data_train, data_val,
                            space=search.space, goal=goal_name,
                            train_steps=train_steps,
                            train_batch=train_batch, seed=seed)
    log(f"[serve] trained+compiled in {time.time()-t0:.1f}s "
        f"(det={winner.train_meta['detection_rate']:.3f} "
        f"fa={winner.train_meta['false_alarm_rate']:.3f})")
    if paged:
        winner.paged = True
        log("[serve] paged KV cache requested — recorded for the token-"
            "serving engine (the classifier forward itself is cache-free)")
    if replicas > 1:
        log(f"[serve] replicating winner onto {replicas} replicas")
        return replicate_winner(winner, replicas, devices=devices,
                                space=search.space)
    return winner
