"""Continuous-batching serving engine (repro.serve) — DESIGN.md §12.

The load-bearing property is *bit-identical greedy parity*: every request
served through the slotted engine (bucketed prefill, mixed lengths in
flight, slot reuse) must produce exactly the tokens a scalar one-request
decode produces.  Everything else — admission, buckets, stats — is tested
around that invariant.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models.registry import build_model
from repro.serve import (
    EngineConfig,
    ReplicaRouter,
    RouterConfig,
    ServeEngine,
    ServeRequest,
    build_buckets,
    gamma_workload,
    greedy_reference,
    onoff_workload,
    poisson_workload,
)
from repro.serve.buckets import pad_batch, pad_length

CACHE_LEN = 48


def _bundle(arch):
    cfg = reduced_config(arch)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, bundle, params


def _requests(cfg, lens_out, seed=0):
    """Mixed (prompt_len, max_new) pairs as a burst workload."""
    rng = np.random.default_rng(seed)
    return [ServeRequest(rid=i,
                         prompt=rng.integers(0, cfg.vocab_size, pl).astype(
                             np.int32),
                         max_new=mn)
            for i, (pl, mn) in enumerate(lens_out)]


def _refs(bundle, params, reqs):
    dec = jax.jit(bundle.decode_step)
    return {r.rid: greedy_reference(bundle, params, r.prompt, r.max_new,
                                    CACHE_LEN, decode_jit=dec)
            for r in reqs}


# ---------------------------------------------------------------- buckets


def test_pad_helpers():
    assert [pad_length(n, 8) for n in (1, 8, 9, 24)] == [8, 8, 16, 24]
    assert [pad_length(n, 1) for n in (1, 7)] == [1, 7]
    assert [pad_batch(n, 8) for n in (1, 2, 3, 5, 8, 11)] == \
        [1, 2, 4, 8, 8, 8]


def test_build_buckets_groups_and_pads():
    prompts = [np.arange(n, dtype=np.int32) for n in (3, 5, 9, 11, 20)]
    buckets = build_buckets(prompts, slots=[0, 1, 2, 3, 4], n_slots=8,
                            pad_to=8, max_batch=4)
    # padded lengths: 8,8,16,16,24 -> three buckets
    by_len = {b.tokens.shape[1]: b for b in buckets}
    assert set(by_len) == {8, 16, 24}
    assert list(by_len[8].lens) == [3, 5]
    # batch rows are padded to powers of two; pad rows scatter out of range
    assert by_len[8].tokens.shape[0] == 2
    b16 = by_len[16]
    assert b16.tokens.shape[0] == 2 and list(b16.slot_idx) == [2, 3]
    # right padding is zeros beyond each row's length
    assert not by_len[8].tokens[0, 3:].any()


def test_build_buckets_chunks_to_max_batch():
    prompts = [np.arange(4, dtype=np.int32)] * 10
    buckets = build_buckets(prompts, slots=list(range(10)), n_slots=16,
                            pad_to=4, max_batch=4)
    assert [len(b.rows) for b in buckets] == [4, 4, 2]
    # every original row appears exactly once across chunks
    assert sorted(i for b in buckets for i in b.rows) == list(range(10))


# ------------------------------------------------------- engine bit-parity


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "zamba2-7b"])
def test_engine_greedy_parity(arch):
    """Burst workload with mixed prompt/output lengths: every request's
    greedy tokens are bit-identical to the scalar one-request reference."""
    cfg, bundle, params = _bundle(arch)
    reqs = _requests(cfg, [(4, 6), (11, 3), (7, 9), (16, 5), (5, 5),
                           (9, 8), (13, 4), (6, 7), (20, 3), (8, 6)])
    refs = _refs(bundle, params, reqs)
    pad_to = 8 if bundle.prefill_pads else 1
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=4, cache_len=CACHE_LEN, pad_to=pad_to, max_prefill_batch=4))
    done = engine.run(reqs)
    assert len(done) == len(reqs)
    for r in done:
        assert r.out == refs[r.rid], f"req {r.rid} diverged"
    # 10 requests through 4 slots exercises slot reuse
    assert engine.prefill_calls >= 3


def test_engine_per_slot_length_independence():
    """Slots at wildly different sequence positions decode together —
    the fix for the shared ``cache['len']`` scalar."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _requests(cfg, [(3, 20), (30, 2), (12, 10), (25, 16)])
    refs = _refs(bundle, params, reqs)
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=4, cache_len=CACHE_LEN, pad_to=1))
    done = engine.run(reqs)
    for r in done:
        assert r.out == refs[r.rid]
    # the longest-running request kept decoding after the others finished
    assert engine.decode_steps >= 19


def test_engine_mid_flight_admission():
    """Virtual-clock arrivals land while earlier requests are mid-decode:
    no wave barrier, and parity still holds."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _requests(cfg, [(6, 12), (9, 12), (7, 10), (5, 8), (11, 6)])
    refs = _refs(bundle, params, reqs)
    # slots=2: rids 0,1 admitted at t=0; the rest arrive mid-decode
    for r, arr in zip(reqs, [0.0, 0.0, 3.0, 4.0, 5.0]):
        r.arrival_s = arr
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=2, cache_len=CACHE_LEN, pad_to=1))
    done = engine.run(reqs)
    for r in done:
        assert r.out == refs[r.rid]
    admits = sorted(r.t_admit for r in done)
    assert admits[0] == 0.0
    # at least one admission happened strictly mid-run (after decode began)
    assert admits[-1] > 0.0


def test_engine_slot_reuse_many_requests():
    """3x more requests than slots: every slot is recycled, FCFS order."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _requests(cfg, [((i % 5) + 4, (i % 3) + 2) for i in range(12)])
    refs = _refs(bundle, params, reqs)
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=4, cache_len=CACHE_LEN, pad_to=8, max_prefill_batch=4))
    done = engine.run(reqs)
    assert [r.rid for r in done] == list(range(12))
    for r in done:
        assert r.out == refs[r.rid]
    # earlier arrivals are admitted no later than later ones (FCFS)
    admits = [r.t_admit for r in sorted(done, key=lambda r: r.rid)]
    assert all(a <= b for a, b in zip(admits, admits[1:]))


def test_engine_padded_prefill_matches_exact():
    """pad_to=8 bucketed prefill must not change a single token vs
    exact-length prefill (right-padding contributes exact zeros)."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _requests(cfg, [(5, 6), (9, 6), (13, 6), (3, 6)])
    exact = ServeEngine(bundle, params, EngineConfig(
        slots=4, cache_len=CACHE_LEN, pad_to=1))
    padded = ServeEngine(bundle, params, EngineConfig(
        slots=4, cache_len=CACHE_LEN, pad_to=8))
    out_e = {r.rid: r.out for r in exact.run(
        [ServeRequest(r.rid, r.prompt, r.max_new) for r in reqs])}
    out_p = {r.rid: r.out for r in padded.run(
        [ServeRequest(r.rid, r.prompt, r.max_new) for r in reqs])}
    assert out_e == out_p
    # padding actually batched prompts into fewer dispatches
    assert padded.prefill_calls <= exact.prefill_calls


def test_engine_truncates_at_cache_capacity():
    cfg, bundle, params = _bundle("qwen2-0.5b")
    req = ServeRequest(rid=0, prompt=np.arange(CACHE_LEN - 3,
                                               dtype=np.int32) % 64,
                       max_new=50)
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=1, cache_len=CACHE_LEN, pad_to=1))
    done = engine.run([req])
    # prompt(45) + out hits cache_len, not the 50-token budget
    assert len(done[0].out) == 3


def test_engine_rejects_unservable_family_and_prompts():
    cfg, bundle, params = _bundle("whisper-tiny")        # encdec
    with pytest.raises(ValueError, match="no slotted serving path"):
        ServeEngine(bundle, params, EngineConfig(slots=2,
                                                 cache_len=CACHE_LEN))
    cfg, bundle, params = _bundle("mamba2-780m")         # pure SSM
    with pytest.raises(ValueError, match="pad_to=1"):
        ServeEngine(bundle, params, EngineConfig(slots=2, pad_to=8,
                                                 cache_len=CACHE_LEN))
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=2, pad_to=1, cache_len=CACHE_LEN))
    with pytest.raises(ValueError, match="exceeds cache_len"):
        engine.submit(ServeRequest(
            rid=0, prompt=np.zeros(CACHE_LEN + 1, np.int32), max_new=1))


# ------------------------------------------- failure semantics (§13)


def test_engine_deadline_expiry_reclaims_slot():
    """A request that blows its latency budget is expired: its partial
    output is a prefix of the reference, the freed slot serves the queue,
    and every surviving request still matches the scalar reference."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _requests(cfg, [(5, 20), (7, 6), (6, 8)])
    refs = _refs(bundle, params, reqs)
    reqs[0].deadline_s = 5.0          # virtual clock: a 5-decode-step budget
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=2, cache_len=CACHE_LEN, pad_to=1))
    done = engine.run(reqs)
    by = {r.rid: r for r in done}
    assert by[0].expired and by[0].done and not by[0].rejected
    assert 0 < len(by[0].out) < 20    # partial, not abandoned silently
    assert by[0].out == refs[0][:len(by[0].out)]
    for rid in (1, 2):                # survivors: exact parity
        assert not by[rid].expired and by[rid].out == refs[rid]
    # the reclaimed slot admitted the queued request mid-run
    assert by[2].t_admit >= 5.0


def test_engine_bounded_queue_rejects_overflow():
    """max_queue=2 with one slot: a burst of 5 bounces 3 explicitly —
    flagged ``rejected``, returned unserved — and the admitted ones still
    decode bit-identically."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _requests(cfg, [(4, 4), (5, 4), (6, 4), (7, 4), (8, 4)])
    refs = _refs(bundle, params, reqs)
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=1, cache_len=CACHE_LEN, pad_to=1, max_queue=2))
    done = engine.run(reqs)
    assert len(done) == 5             # every request comes back exactly once
    served = [r for r in done if not r.rejected]
    bounced = [r for r in done if r.rejected]
    assert [r.rid for r in bounced] == [2, 3, 4]
    assert all(not r.out and not r.done for r in bounced)
    assert len(engine.rejected) == 3
    for r in served:
        assert r.out == refs[r.rid]


def test_engine_drain_completes_in_flight_only():
    """Graceful shutdown: drain() decodes the in-flight requests to
    completion (bit-identical) without touching the admission queue."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _requests(cfg, [(5, 8), (9, 6), (6, 10)])
    refs = _refs(bundle, params, reqs)
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=2, cache_len=CACHE_LEN, pad_to=1))
    for r in reqs:
        assert engine.submit(r)       # unbounded queue: all accepted
    engine._admit(0.0)                # rids 0,1 in flight; rid 2 queued
    engine.step(0.0)                  # mid-decode when the drain begins
    done = engine.drain()
    assert {r.rid for r in done} == {0, 1}
    for r in done:
        assert not r.expired and r.out == refs[r.rid]
    assert [r.rid for r in engine.waiting] == [2]   # held for the caller
    assert all(s is None for s in engine.active)


# -------------------------------------------------------------- load gen


def test_poisson_workload_deterministic():
    a = poisson_workload(8, vocab_size=64, rate_per_s=10.0, seed=3)
    b = poisson_workload(8, vocab_size=64, rate_per_s=10.0, seed=3)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    arr = [r.arrival_s for r in a]
    assert arr == sorted(arr) and arr[-1] > 0
    burst = poisson_workload(4, vocab_size=64, rate_per_s=0.0)
    assert all(r.arrival_s == 0.0 for r in burst)


# --------------------------------------------------------- winner serving


@pytest.mark.slow
def test_serve_winner_end_to_end(tiny_ecg):
    """search → select_for_goal → train+compile → serve: the closed loop."""
    from repro.core.evolution import EvolutionarySearch, NASConfig
    from repro.serve import serve_winner
    (tr, va) = tiny_ecg
    cfg = NASConfig(generations=1, children_per_gen=3, n_accept=2,
                    init_population=3, train_steps=60, train_batch=32,
                    n_workers=2, seed=0, det_min=0.5, fa_max=0.5)
    search = EvolutionarySearch(cfg, tr, va, log=lambda *_: None)
    state = search.run()
    winner = serve_winner(search, state, "low_energy",
                          data_train=tr, data_val=va,
                          train_steps=60, train_batch=32,
                          log=lambda *_: None)
    x_va = va[0][:10]
    logits = winner.predict(x_va)
    assert logits.shape == (10, 2)
    assert np.isfinite(logits).all()
    preds = winner.classify(x_va)
    assert set(np.unique(preds)) <= {0, 1}
    assert winner.batches_served == 2
    assert "goal=low_energy" in winner.report()


def test_serve_winner_raises_without_feasible(tiny_ecg):
    from repro.core.evolution import EvolutionarySearch, NASConfig
    from repro.core.objective_schema import Constraints, DesignGoal
    from repro.serve import serve_winner
    (tr, va) = tiny_ecg
    cfg = NASConfig(generations=0, children_per_gen=2, n_accept=1,
                    init_population=2, train_steps=5, train_batch=16,
                    n_workers=1, seed=0)
    search = EvolutionarySearch(cfg, tr, va, log=lambda *_: None)
    state = search.run()
    impossible = DesignGoal(name="impossible",
                            constraints=Constraints(det_min=1.01))
    with pytest.raises(LookupError, match="no feasible candidate"):
        serve_winner(search, state, impossible, data_train=tr, data_val=va)


# ------------------------------------------- engine replication hooks (§14)


def test_engine_cancel_and_take_finished():
    """The router-facing surface: cancel withdraws in-flight and queued
    requests without recording a result; take_finished drains completions
    incrementally; the load metrics track slot occupancy."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _requests(cfg, [(5, 8), (9, 6), (6, 10)])
    refs = _refs(bundle, params, reqs)
    engine = ServeEngine(bundle, params, EngineConfig(
        slots=2, cache_len=CACHE_LEN, pad_to=1))
    engine.reset()
    for r in reqs:
        engine.submit(r)
    assert engine.has_work and engine.queue_depth == 3
    engine._admit(0.0)                # rids 0,1 in flight; rid 2 queued
    assert [r.rid for r in engine.in_flight] == [0, 1]
    assert engine.queue_depth == 1
    assert engine.cancel(2) is reqs[2]        # queued: leaves the queue
    assert engine.cancel(1) is reqs[1]        # in flight: slot reclaimed
    assert engine.cancel(99) is None          # unknown rid: no-op
    assert [r.rid for r in engine.in_flight] == [0]
    while engine.has_work:
        engine.tick(float(engine.decode_steps))
    got = engine.take_finished()
    assert [r.rid for r in got] == [0] and got[0].out == refs[0]
    assert engine.take_finished() == []       # drained: second take is empty
    assert not engine.has_work


# ------------------------------------------------- replica router (§14)


def _router_requests(cfg, triples, seed=0):
    """(prompt_len, max_new, arrival_s) triples."""
    rng = np.random.default_rng(seed)
    return [ServeRequest(rid=i,
                         prompt=rng.integers(0, cfg.vocab_size, pl).astype(
                             np.int32),
                         max_new=mn, arrival_s=arr)
            for i, (pl, mn, arr) in enumerate(triples)]


def test_router_greedy_parity_no_faults():
    """Fault-free baseline: requests split across two replicas (open-loop
    arrivals, mixed lengths) each decode bit-identically to the scalar
    reference, and the router's accounting balances."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _router_requests(cfg, [(4, 6, 0.0), (8, 5, 0.0), (6, 4, 2.0),
                                  (5, 7, 3.0), (7, 3, 5.0), (4, 6, 8.0)])
    refs = _refs(bundle, params, reqs)
    router = ReplicaRouter(bundle, params, RouterConfig(
        replicas=2, engine=EngineConfig(slots=2, cache_len=CACHE_LEN,
                                        pad_to=4, max_prefill_batch=2)))
    done = router.run(reqs)
    assert [r.rid for r in done] == list(range(6))
    for r in done:
        assert not r.rejected and not r.expired
        assert r.out == refs[r.rid]
    s = router.stats
    assert s["admitted"] == s["completed"] == s["dispatches"] == 6
    assert s["failovers"] == s["restarts"] == 0 and s["quarantined"] == []
    # both replicas actually served work (least-loaded spreads the burst)
    assert all(rep.engine.decode_steps > 0 for rep in router.replicas)


def test_router_queue_shedding_is_explicit():
    """A burst over the bounded router queue: overflow is bounced at
    admission — flagged ``rejected``, returned unserved, counted — and
    every admitted request still decodes bit-identically.  Zero silent
    drops: submitted == served + shed."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _router_requests(cfg, [(4, 4, 0.0)] * 10, seed=1)
    refs = _refs(bundle, params, reqs)
    router = ReplicaRouter(bundle, params, RouterConfig(
        replicas=1, max_queue=4,
        engine=EngineConfig(slots=2, cache_len=CACHE_LEN, pad_to=4,
                            max_prefill_batch=2)))
    done = router.run(reqs)
    assert len(done) == 10            # every request back exactly once
    shed = [r for r in done if r.rejected]
    served = [r for r in done if not r.rejected]
    assert len(shed) == router.stats["shed_queue"] == 6
    assert all(not r.out and not r.done for r in shed)
    for r in served:
        assert r.out == refs[r.rid]
    assert router.stats["admitted"] == 4
    assert router.stats["completed"] == len(served) == 4


def test_router_deadline_shedding_rejects_unmeetable():
    """Deadline-aware admission: once observed service times prove a
    deadline unmeetable from the back of the queue, the request is bounced
    up front instead of being admitted to die."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    # warmup (no deadlines) seeds the service-time estimate at ~3 virtual
    # seconds; then a burst with 1s budgets — provably unmeetable for
    # anything that has to queue
    warm = _router_requests(cfg, [(4, 3, 0.0), (4, 3, 4.0), (4, 3, 8.0)],
                            seed=2)
    burst = _router_requests(cfg, [(4, 3, 20.0)] * 6, seed=3)
    for i, r in enumerate(burst):
        r.rid = 10 + i
        r.deadline_s = 1.0
    router = ReplicaRouter(bundle, params, RouterConfig(
        replicas=1, engine=EngineConfig(slots=1, cache_len=CACHE_LEN,
                                        pad_to=4, max_prefill_batch=1)))
    done = router.run(warm + burst)
    assert len(done) == 9             # zero silent drops
    s = router.stats
    assert s["shed_deadline"] == 5    # queue-empty head admitted, rest shed
    shed = [r for r in done if r.rejected]
    assert len(shed) == 5 and all(not r.out for r in shed)
    # warmups completed; the one admitted burst request expired in flight
    # (1s budget vs ~3s service) — expired, never silently dropped
    assert s["completed"] == 3 and s["expired"] == 1


def test_router_hedges_straggler_first_completion_wins():
    """A silent stall with the heartbeat effectively off: the hedge path
    alone must rescue the stuck requests — stragglers past the seeded
    service-time percentile are twinned onto the healthy replica, the twin
    wins, and the output is still bit-identical."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    from repro.core.faults import FaultPlan, FaultSpec
    reqs = _router_requests(cfg, [(4, 4, float(i)) for i in range(20)],
                            seed=4)
    refs = _refs(bundle, params, reqs)
    plan = FaultPlan([FaultSpec(site="serve.replica", kind="stall",
                                hang_s=30.0, times=1,
                                when=lambda c: c["replica"] == 0
                                and c["tick"] == 12)])
    router = ReplicaRouter(bundle, params, RouterConfig(
        replicas=2, hedge=True, hedge_percentile=90.0, hedge_min_samples=4,
        heartbeat_misses=50,          # heartbeat off: hedging must carry it
        engine=EngineConfig(slots=2, cache_len=CACHE_LEN, pad_to=4,
                            max_prefill_batch=2)), faults=plan)
    done = router.run(reqs)
    assert len(done) == 20
    for r in done:
        assert not r.rejected and not r.expired
        assert r.out == refs[r.rid]
    s = router.stats
    assert s["hedges"] >= 1 and s["hedge_wins"] >= 1
    assert s["quarantined"] == []     # nobody died — just a straggler


def test_router_drain_completes_in_flight_only():
    """Graceful shutdown across the replica set: drain() finishes the
    dispatched requests bit-identically and leaves the undispatched queue
    for the caller."""
    cfg, bundle, params = _bundle("qwen2-0.5b")
    reqs = _router_requests(cfg, [(4, 5, 0.0)] * 8, seed=5)
    refs = _refs(bundle, params, reqs)
    router = ReplicaRouter(bundle, params, RouterConfig(
        replicas=2, engine=EngineConfig(slots=2, cache_len=CACHE_LEN,
                                        pad_to=4, max_prefill_batch=2)))
    router.reset()
    for r in reqs:
        assert router.submit(r)
    router._dispatch(0.0)             # 4 slots filled, 4 left queued
    drained = router.drain()
    assert {r.rid for r in drained} == {0, 1, 2, 3}
    for r in drained:
        assert not r.expired and r.out == refs[r.rid]
    assert [r.rid for r in router.queue] == [4, 5, 6, 7]   # held


# ------------------------------------------------ load generators (§14)


def test_gamma_workload_deterministic_heavy_tail():
    a = gamma_workload(64, vocab_size=64, rate_per_s=2.0, cv=4.0, seed=3)
    b = gamma_workload(64, vocab_size=64, rate_per_s=2.0, cv=4.0, seed=3)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    arr = np.array([r.arrival_s for r in a])
    assert (np.diff(arr) >= 0).all() and arr[-1] > 0
    # heavy tail: the burstier stream has a larger max/median gap ratio
    gaps_hi = np.diff([r.arrival_s for r in a])
    gaps_lo = np.diff([r.arrival_s for r in gamma_workload(
        64, vocab_size=64, rate_per_s=2.0, cv=1.0, seed=3)])
    assert gaps_hi.max() > gaps_lo.max()
    with pytest.raises(ValueError, match="rate_per_s"):
        gamma_workload(4, vocab_size=64, rate_per_s=0.0)
    with pytest.raises(ValueError, match="variation"):
        gamma_workload(4, vocab_size=64, rate_per_s=1.0, cv=-1.0)


def test_onoff_workload_bursts_inside_on_windows():
    a = onoff_workload(40, vocab_size=64, rate_per_s=5.0, on_s=2.0,
                       off_s=3.0, seed=9)
    b = onoff_workload(40, vocab_size=64, rate_per_s=5.0, on_s=2.0,
                       off_s=3.0, seed=9)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    arr = np.array([r.arrival_s for r in a])
    assert (np.diff(arr) >= 0).all()
    # every arrival lands strictly inside an on window of the 5s period
    assert ((arr % 5.0) < 2.0).all()
    with pytest.raises(ValueError, match="onoff"):
        onoff_workload(4, vocab_size=64, rate_per_s=5.0, on_s=0.0, off_s=1.0)


# ------------------------------------------------ replicated winner (§14)


def test_replicated_winner_parity_and_failover(tiny_ecg):
    """Replicated classification dispatch: round-robin replicas return the
    same logits as the single winner; a replica that keeps crashing fails
    over mid-call (same batch, same logits) and is quarantined — last-live
    protection keeps the survivor."""
    from repro.core.faults import FaultPlan, FaultSpec
    from repro.core.genome import random_genome
    from repro.serve import compile_winner, replicate_winner
    (tr, va) = tiny_ecg
    g = random_genome(np.random.default_rng(0))
    winner = compile_winner(g, tr, va, train_steps=20, train_batch=32,
                            seed=0, goal="test")
    x = va[0][:10]
    ref = winner.predict(x)

    rw = replicate_winner(winner, 2)
    assert np.array_equal(rw.predict(x), ref)
    assert np.array_equal(rw.predict(x), ref)   # round-robins to replica 1
    assert [r.batches_served for r in rw.replicas] == [1, 1]
    assert rw.live_replicas == [0, 1]

    plan = FaultPlan([FaultSpec(site="router.dispatch", kind="crash",
                                when=lambda c: c["replica"] == 0)])
    rw2 = replicate_winner(winner, 2, faults=plan)
    for _ in range(8):
        assert np.array_equal(rw2.predict(x), ref)  # failover: same logits
    assert rw2.stats["failovers"] >= 1
    assert rw2.stats["quarantined"] == [0]
    assert rw2.live_replicas == [1]             # last live: never retired
    assert "replicas=1/2" in rw2.report()
    with pytest.raises(ValueError, match="at least one replica"):
        replicate_winner(winner, 0)
