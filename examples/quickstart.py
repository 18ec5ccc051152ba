"""Quickstart: HALF's hardware-aware NAS on the synthetic ECG task.

This is the paper's end-to-end flow at laptop scale: dataset -> evolutionary
hardware-aware NAS (cheap analytic objectives + trained detection rates) ->
Pareto frontier -> deployable compiled candidate (BN-folded, quantized,
with an unrolling plan and accumulator formats).

Run:  PYTHONPATH=src python examples/quickstart.py [--generations 6]
"""
import argparse
import time

import jax
import numpy as np

from repro.core.compile_model import compile_candidate
from repro.core.evolution import EvolutionarySearch, NASConfig
from repro.core.genome import describe
from repro.core.trainer import init_candidate
from repro.data.ecg import make_ecg_dataset, train_val_split


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--generations", type=int, default=6)
    ap.add_argument("--samples", type=int, default=600)
    ap.add_argument("--train-steps", type=int, default=150)
    args = ap.parse_args()

    t0 = time.time()
    print("== generating synthetic Charité-style ECG dataset ==")
    x, y = make_ecg_dataset(seed=0, n_samples=args.samples, decimation=16)
    data_train, data_val = train_val_split(x, y)
    print(f"   {x.shape} in {time.time()-t0:.1f}s")

    cfg = NASConfig(
        generations=args.generations, children_per_gen=8, n_accept=4,
        init_population=6, train_steps=args.train_steps, train_batch=32,
        n_workers=2, seed=0,
    )
    search = EvolutionarySearch(cfg, data_train, data_val)
    state = search.run()

    print("\n== Pareto-frontier solutions per design goal (paper §VI-B) ==")
    for goal in ("low_energy", "low_power", "high_throughput"):
        sol = search.select_for_goal(state, goal)
        if sol is None:
            print(f"-- {goal}: no feasible candidate yet "
                  f"(needs more generations)")
            continue
        det = 1.0 - sol.expensive[0]
        print(f"\n-- best for {goal} "
              f"(detection={det:.3f}, false alarm={sol.expensive[1]:.3f}):")
        print(describe(sol.genome))

    sol = search.select_solution(state) or max(
        state.population, key=lambda c: -(c.expensive[0] if c.trained else 1))
    print("\n== compiling the selected candidate for deployment ==")
    specs = sol.genome.phenotype()
    params = init_candidate(jax.random.PRNGKey(0), specs)
    calib = jax.numpy.asarray(
        data_val[0][:32, ::data_val[0].shape[1] // sol.genome.input_length()]
        [:, :sol.genome.input_length()])
    compiled = compile_candidate(sol.genome, params, calib)
    print(compiled.report())
    print(f"\nestimates: min-alpha {compiled.estimate_min.throughput_sps:.0f}"
          f" samples/s @ {compiled.estimate_min.p_total_w:.2f} W | max-alpha "
          f"{compiled.estimate_max.throughput_sps:.0f} samples/s @ "
          f"{compiled.estimate_max.p_total_w:.2f} W")

    # -- serving the deployment artifact (repro.serve, DESIGN.md §12) --
    # One jitted deployment-mode forward answers batched requests; inputs
    # at full resolution are decimated to the genome's input length and
    # the batch is padded to a power of two so repeated serving reuses a
    # handful of compiled executables.  The full closed loop — winner
    # *trained to convergence* before compiling — is
    # examples/serve_winner.py.
    #
    # Token-level LM serving has a paged KV cache (DESIGN.md §15):
    # serve_winner(..., paged=True) records the preference on the handle
    # (the classifier forward itself is cache-free) and
    # launch/serve.py --paged builds EngineConfig(paged=True) —
    # admission on free pool *blocks* rather than worst-case dense
    # slots, so more requests fit at equal memory on long-tail prompts.
    # Prefer dense slots (the default) when prompts uniformly fill
    # cache_len or an admitted request must never be OOM-shed.
    print("\n== serving batched requests through the compiled forward ==")
    from repro.core.trainer import forward
    from repro.serve import ServableWinner
    winner = ServableWinner(
        genome=sol.genome, compiled=compiled, goal=None,
        input_length=sol.genome.input_length(),
        train_meta={"detection_rate": float("nan"),
                    "false_alarm_rate": float("nan"), "val_loss": 0.0,
                    "steps": 0.0},
        _predict=jax.jit(lambda xb: forward(compiled.params, specs, xb,
                                            quant=None, train=False)))
    t = time.time()
    preds = winner.classify(data_val[0][:32])
    dt = time.time() - t
    t = time.time()
    winner.classify(data_val[0][32:64])
    dt_warm = time.time() - t
    print(f"   32-window batch: {dt*1e3:.0f} ms cold (compile), "
          f"{dt_warm*1e3:.0f} ms warm; classes={np.bincount(preds).tolist()}")

    # -- resilient serving (repro.serve.router, DESIGN.md §14) --
    # The always-on deployment fronts N replicas of the winner behind one
    # predict() that health-checks, fails over, and quarantines — here we
    # *inject* a crash on replica 0's first batch to show the failover is
    # invisible to the caller (same classes; repeated failures would
    # quarantine the replica).  The
    # token-level analogue for LM serving is ReplicaRouter
    # (launch/serve.py --router --replicas 2), chaos-tested in
    # tests/test_faults.py against a bit-identical greedy reference.
    print("\n== resilient serving: replicated winner with injected crash ==")
    from repro.core.faults import FaultPlan, FaultSpec
    from repro.serve import replicate_winner
    faults = FaultPlan([FaultSpec(site="router.dispatch", kind="crash",
                                  at=(1,))], seed=0)
    replicated = replicate_winner(winner, replicas=2, faults=faults)
    preds_rep = replicated.classify(data_val[0][:32])
    assert np.array_equal(preds, preds_rep)
    print(f"   crash injected on replica 0 -> failover; "
          f"stats={replicated.stats} (classes unchanged)")
    print(f"total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
