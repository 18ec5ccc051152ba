"""Chaos suite for the fault-injection harness (DESIGN.md §13).

The load-bearing property is *determinism under failure*: with a seeded
:class:`FaultPlan` wired through the explicit inject points, a search that
suffers worker crashes, NaN candidates, corrupt checkpoints or preemption
recovers to a trajectory that is bit-identical (deterministic pipelines)
or structurally valid (async) versus the fault-free run.
"""
import json

import numpy as np
import pytest

from repro.core.evolution import EvolutionarySearch, NASConfig
from repro.core.faults import (
    DeviceLost,
    FaultPlan,
    FaultSpec,
    InjectedCrash,
    Preemption,
    crash_every,
    device_loss_every,
    nan_candidate_every,
    stall_every,
)
from repro.core.scheduler import DynamicScheduler
from repro.core.trainer import TrainResult


# --------------------------------------------------------------- harness


def test_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(site="x", kind="meteor", every=1)
    with pytest.raises(ValueError, match="trigger"):
        FaultSpec(site="x", kind="crash")


def test_hit_counters_at_every_and_times():
    plan = FaultPlan([FaultSpec(site="a", kind="crash", at=(2,)),
                      FaultSpec(site="b", kind="nonfinite", every=3,
                                times=2)])
    # `at` fires on exactly the named 1-based hit
    assert [plan.check("a") is not None for _ in range(4)] == \
        [False, True, False, False]
    # `every` fires on multiples, capped by `times`
    fired_b = [plan.check("b") is not None for _ in range(12)]
    assert [i + 1 for i, f in enumerate(fired_b) if f] == [3, 6]
    assert plan.hits("a") == 4 and plan.hits("b") == 12
    log = plan.fired()
    assert [(e.site, e.hit) for e in log] == [("a", 2), ("b", 3), ("b", 6)]
    assert plan.fired(site="b", kind="nonfinite") == log[1:]


def test_when_predicate_gates_and_pure_when_fires_every_match():
    plan = FaultPlan([FaultSpec(site="s", kind="crash",
                                when=lambda c: c.get("job_id") == 7)])
    assert plan.check("s", job_id=3) is None
    assert plan.check("s", job_id=7) is not None
    assert plan.check("s", job_id=7) is not None  # no counter trigger: every
    assert len(plan.fired("s")) == 2              # accepted hit fires


def test_fire_actions_by_kind():
    plan = FaultPlan([FaultSpec(site="c", kind="crash", at=(1,)),
                      FaultSpec(site="d", kind="device_loss", at=(1,)),
                      FaultSpec(site="p", kind="preempt", at=(1,)),
                      FaultSpec(site="n", kind="nonfinite", at=(1,)),
                      FaultSpec(site="h", kind="hang", hang_s=0.0,
                                at=(1,))])
    with pytest.raises(InjectedCrash):
        plan.fire("c")
    with pytest.raises(DeviceLost):
        plan.fire("d")
    with pytest.raises(Preemption):        # a KeyboardInterrupt subclass
        plan.fire("p")
    assert issubclass(Preemption, KeyboardInterrupt)
    assert isinstance(DeviceLost("x"), InjectedCrash)
    spec = plan.fire("n")                  # data kind: returned, not raised
    assert spec is not None and spec.kind == "nonfinite"
    assert plan.fire("h").kind == "hang"   # slept 0s, returned
    assert plan.fire("c") is None          # at=(1,) spent


def test_corrupt_file_is_deterministic(tmp_path):
    blob = bytes(range(200))
    for mode in ("truncate", "garbage"):
        out = []
        for trial in range(2):
            p = tmp_path / f"{mode}{trial}.bin"
            p.write_bytes(blob)
            FaultPlan(seed=11).corrupt_file(str(p), mode=mode)
            out.append(p.read_bytes())
        assert out[0] == out[1] and out[0] != blob
        assert out[0][:100] == blob[:100]  # first half survives
    with pytest.raises(ValueError, match="corruption mode"):
        FaultPlan().corrupt_file(str(tmp_path / "truncate0.bin"),
                                 mode="nibble")


# ------------------------------------------------------------- scheduler


def test_scheduler_retries_injected_crashes_to_completion():
    """Every 3rd job's first attempt crashes; retries with backoff finish
    the batch with values identical to a fault-free run."""
    plan = FaultPlan([crash_every(3)])
    sched = DynamicScheduler(n_workers=3, max_retries=2, speculate=False,
                             backoff_base_s=0.001, faults=plan)
    jobs = [lambda i=i: i * i for i in range(12)]
    run = sched.submit(jobs)
    res = run.wait()
    assert [r.job_id for r in res] == list(range(12))
    assert all(r.ok for r in res)
    assert [r.value for r in res] == [i * i for i in range(12)]
    crashed = {e.ctx["job_id"] for e in plan.fired(kind="crash")}
    assert crashed == {2, 5, 8, 11}
    for r in res:
        assert r.attempts == (2 if r.job_id in crashed else 1)
    assert run.stats["retries"] == 4 and run.stats["backoff_s"] > 0.0


def test_device_loss_quarantines_and_rebalances():
    """One DeviceLost retires its device instantly: its worker exits and
    every job lands on the surviving device."""
    plan = FaultPlan([FaultSpec(site="scheduler.job", kind="device_loss",
                                when=lambda c: c["device"] == "dev:0",
                                times=1)])
    sched = DynamicScheduler(n_workers=2, max_retries=2, speculate=False,
                             devices=["dev:0", "dev:1"],
                             backoff_base_s=0.001, faults=plan)
    run = sched.submit([lambda device=None, i=i: i for i in range(8)])
    res = run.wait()
    assert all(r.ok for r in res) and len(res) == 8
    assert run.quarantined == ["dev:0"]
    assert run.stats["quarantined"] == 1
    assert {r.device for r in res} == {"dev:1"}  # rebalanced onto survivor


def test_last_live_device_is_never_quarantined():
    """DeviceLost on every first attempt, but with a single device the
    scheduler must keep it: partial progress beats none."""
    plan = FaultPlan([FaultSpec(site="scheduler.job", kind="device_loss",
                                when=lambda c: c["attempt"] == 1)])
    sched = DynamicScheduler(n_workers=2, max_retries=2, speculate=False,
                             devices=["dev:0"], backoff_base_s=0.001,
                             faults=plan)
    res = sched.run([lambda device=None, i=i: i for i in range(6)])
    assert all(r.ok for r in res) and len(res) == 6
    assert all(r.attempts == 2 for r in res)


def test_device_loss_every_drill_helper():
    """device_loss_every mirrors crash_every (job-keyed, so deterministic
    under any worker interleaving) but retires the device instead of just
    failing the attempt."""
    plan = FaultPlan([device_loss_every(5, times=1)])
    sched = DynamicScheduler(n_workers=2, max_retries=2, speculate=False,
                             devices=["dev:0", "dev:1"],
                             backoff_base_s=0.001, faults=plan)
    run = sched.submit([lambda device=None, i=i: i for i in range(8)])
    res = run.wait()
    assert all(r.ok for r in res) and len(res) == 8
    events = plan.fired(kind="device_loss")
    assert [e.ctx["job_id"] for e in events] == [4]   # every 5th job
    assert run.stats["quarantined"] == 1              # device retired


def test_stall_every_drill_helper():
    """stall_every schedules counter-keyed stalls: the clock-owning caller
    receives the spec (check, never fire) and advances its own time."""
    spec = stall_every(3, 2.5)
    assert (spec.site, spec.kind, spec.hang_s) == ("serve.decode", "stall",
                                                   2.5)
    plan = FaultPlan([stall_every(3, 2.5, site="serve.replica", times=2)])
    hits = [plan.check("serve.replica", replica=0, tick=t, step=t)
            for t in range(9)]
    assert [i + 1 for i, s in enumerate(hits) if s is not None] == [3, 6]
    assert all(s.hang_s == 2.5 for s in hits if s is not None)


# ------------------------------------------------------- search-level chaos


def _det_batch_trainer():
    def train(genomes, device=None):
        out = []
        for g in genomes:
            det = min(0.99, 0.70 + 0.05 * g.depth())
            out.append(TrainResult(
                detection_rate=det,
                false_alarm_rate=max(0.0, 0.3 - 0.04 * g.depth()),
                val_loss=0.2, steps=0))
        return out
    return train


def _search(pipeline="off", seed=3, faults=None, log=None, **kw):
    kw.setdefault("generations", 4)
    cfg = NASConfig(children_per_gen=10, n_accept=4,
                    init_population=8, population_cap=16, n_workers=2,
                    seed=seed, pipeline=pipeline, **kw)
    return EvolutionarySearch(cfg, None, None,
                              batch_train_fn=_det_batch_trainer(),
                              log=log or (lambda *_: None), faults=faults)


def _assert_same_trajectory(a, b):
    assert a.generation == b.generation
    assert list(a.pop.phash) == list(b.pop.phash)
    np.testing.assert_array_equal(a.pop.cheap, b.pop.cheap)
    np.testing.assert_array_equal(a.pop.expensive, b.pop.expensive)
    np.testing.assert_array_equal(a.pop.born, b.pop.born)
    assert set(a.evaluated_hashes) == set(b.evaluated_hashes)
    for h in a.evaluated_hashes:
        np.testing.assert_array_equal(a.evaluated_hashes[h],
                                      b.evaluated_hashes[h])
    for ra, rb in zip(a.history, b.history):
        for k in ("generation", "children", "trained", "population",
                  "front_size", "feasible", "best_primary"):
            assert ra[k] == rb[k] or (
                np.isnan(ra[k]) and np.isnan(rb[k])), k


def test_search_is_bit_identical_under_crash_and_retry():
    """The acceptance drill: a worker crash every 3rd job, retried by the
    scheduler, must not perturb a single bit of the search trajectory."""
    ref = _search().run()
    plan = FaultPlan([crash_every(3)])
    faulted = _search(faults=plan).run()
    assert plan.fired("scheduler.job", kind="crash")  # faults really fired
    _assert_same_trajectory(ref, faulted)


def test_nan_candidate_quarantined_bucket_mates_survive():
    """One injected non-finite training result: that candidate lands at the
    schema-pessimistic row while every bucket-mate keeps the exact values
    of the fault-free run."""
    ref = _search().init_state()
    plan = FaultPlan([nan_candidate_every(5, times=1)])
    lines = []
    state = _search(faults=plan, log=lambda *a: lines.append(
        " ".join(str(x) for x in a))).init_state()
    events = plan.fired("trainer.result", kind="nonfinite")
    assert len(events) == 1
    bad = events[0].ctx["phash"]
    assert any("diverged" in ln and "quarantined" in ln for ln in lines)
    assert list(state.pop.phash) == list(ref.pop.phash)
    s = _search()
    worst = s._exp_worst
    for i, h in enumerate(state.pop.phash):
        if str(h) == bad:
            np.testing.assert_array_equal(state.pop.expensive[i], worst)
        else:
            np.testing.assert_array_equal(state.pop.expensive[i],
                                          ref.pop.expensive[i])
    # the pessimistic row also reached the dormant-gene cache (the
    # candidate is never retrained, like any permanently failed one)
    np.testing.assert_array_equal(state.evaluated_hashes[bad], worst)


def test_checkpoint_corruption_falls_back_to_rotated_prev(tmp_path):
    """An injected torn write on the final checkpoint: load_state warns,
    falls back to `<path>.prev`, and the resumed search finishes
    bit-identically to the uninterrupted one."""
    path = str(tmp_path / "ckpt.json")
    # saves: init (hit 1), gen1 (2), gen2 (3 -> corrupted on disk)
    plan = FaultPlan([FaultSpec(site="ckpt.save", kind="corrupt", at=(3,))])
    final = _search(generations=2, faults=plan).run_resumable(path)
    assert final.generation == 2
    with pytest.raises(json.JSONDecodeError):
        json.load(open(path))               # the write really is torn
    lines = []
    restored = _search(generations=2,
                       log=lambda *a: lines.append(
                           " ".join(str(x) for x in a))).load_state(path)
    assert any("corrupt" in ln and ".prev" in ln for ln in lines)
    assert restored.generation == 1         # one generation lost, not all
    resumed = _search(generations=2).run_resumable(path)
    _assert_same_trajectory(final, resumed)


def test_corrupt_checkpoint_without_prev_still_raises(tmp_path):
    path = str(tmp_path / "ckpt.json")
    with open(path, "w") as f:
        f.write('{"generation": 1, "hist')    # torn write, no rotation yet
    with pytest.raises(json.JSONDecodeError):
        _search().load_state(path)


def test_both_checkpoints_torn_raises_clean_error(tmp_path):
    """The double fault: `<path>` AND `<path>.prev` both torn.  The caller
    gets one clean RuntimeError naming both files and both parse errors —
    never a raw traceback from mid-parse of the fallback."""
    path = str(tmp_path / "ckpt.json")
    with open(path, "w") as f:
        f.write('{"generation": 2, "hist')            # torn current
    with open(path + ".prev", "w") as f:
        f.write('{"generation": 1, "population": [')  # torn previous
    with pytest.raises(RuntimeError,
                       match="both checkpoints are corrupt") as exc:
        _search().load_state(path)
    msg = str(exc.value)
    assert path in msg and path + ".prev" in msg
    assert "JSONDecodeError" in msg
    # the underlying parse error is chained for debugging, not surfaced raw
    assert isinstance(exc.value.__cause__, json.JSONDecodeError)


def test_two_consecutive_torn_save_cycles_fall_back(tmp_path):
    """Two run→torn-final-save cycles in a row: each cycle's rotation keeps
    one good generation behind the torn write, so each load falls back
    cleanly and the twice-resumed search is bit-identical to an
    uninterrupted one."""
    path = str(tmp_path / "ckpt.json")
    # cycle 1: saves init(1), gen1(2), gen2(3 -> torn)
    plan1 = FaultPlan([FaultSpec(site="ckpt.save", kind="corrupt", at=(3,))])
    _search(generations=2, faults=plan1).run_resumable(path)
    lines = []
    log = lambda *a: lines.append(" ".join(str(x) for x in a))  # noqa: E731
    assert _search(generations=2, log=log).load_state(path).generation == 1
    assert any("corrupt" in ln and ".prev" in ln for ln in lines)
    # cycle 2: resume from the fallback cut and run to generation 3; the
    # resumed run saves gen2(1), gen3(2 -> torn again)
    plan2 = FaultPlan([FaultSpec(site="ckpt.save", kind="corrupt", at=(2,))])
    _search(generations=3, faults=plan2).run_resumable(path)
    with pytest.raises(json.JSONDecodeError):
        json.load(open(path))                 # the final write really tore
    lines2 = []
    log2 = lambda *a: lines2.append(" ".join(str(x) for x in a))  # noqa: E731
    restored = _search(generations=3, log=log2).load_state(path)
    assert any("corrupt" in ln and ".prev" in ln for ln in lines2)
    assert restored.generation == 2           # cycle 2's good rotation
    # a third resume completes the search bit-identically
    ref = _search(generations=3).run_resumable(str(tmp_path / "ref.json"))
    final = _search(generations=3).run_resumable(path)
    _assert_same_trajectory(ref, final)


def test_graceful_preemption_resumes_bit_identically(tmp_path):
    """Injected SIGTERM at generation 2: run_resumable persists the last
    consistent state, re-raises, and a fresh process completes the search
    bit-identically to one that was never preempted."""
    ref = _search().run_resumable(str(tmp_path / "ref.json"))
    path = str(tmp_path / "ckpt.json")
    plan = FaultPlan([FaultSpec(site="search.generation", kind="preempt",
                                when=lambda c: c["generation"] == 2,
                                times=1)])
    with pytest.raises(KeyboardInterrupt):
        _search(faults=plan).run_resumable(path)
    mid = _search().load_state(path)
    assert mid.generation == 2              # the last completed generation
    resumed = _search().run_resumable(path)
    _assert_same_trajectory(ref, resumed)


def test_async_preemption_resumes_to_valid_front(tmp_path):
    """Preempting the async pipeline mid-flight: the checkpoint holds the
    last consistent drained cut; resuming completes to target with every
    structural invariant intact (async trades bit-parity for overlap)."""
    from repro.core.pareto import pareto_front
    path = str(tmp_path / "ckpt.json")
    plan = FaultPlan([FaultSpec(site="search.generation", kind="preempt",
                                when=lambda c: c["generation"] >= 2,
                                times=1)])
    with pytest.raises(KeyboardInterrupt):
        _search(pipeline="async", faults=plan).run_resumable(path)
    mid = _search(pipeline="async").load_state(path)
    assert 2 <= mid.generation < 4
    assert mid.pop.trained_mask.all()       # the cut is consistent
    final = _search(pipeline="async").run_resumable(path)
    assert final.generation == 4
    assert len(set(final.pop.phash)) == len(final.pop)
    assert final.pop.trained_mask.all()
    objs = np.stack([c.objective_vector() for c in final.population])
    assert len(pareto_front(objs)) >= 1
    assert all(r.get("pipeline") == "async" for r in final.history)


# ------------------------------------------------- router chaos (§14)


def _serve_setup():
    import jax
    from repro.configs import reduced_config
    from repro.models.registry import build_model
    cfg = reduced_config("qwen2-0.5b")
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, bundle, params


def test_router_chaos_parity_under_replica_loss_and_stall():
    """THE acceptance drill (ISSUE 9): a seeded FaultPlan kills one replica
    mid-decode (device_loss → quarantine + failover) and silently stalls
    another (heartbeat → evict + restart).  Every admitted request must
    come back bit-identical to the fault-free greedy reference; requests
    shed at the bounded queue are explicitly rejected with counts
    asserted; zero silent drops."""
    from repro.serve import (EngineConfig, ReplicaRouter, RouterConfig,
                             ServeRequest, greedy_reference)
    cfg, bundle, params = _serve_setup()
    rng = np.random.default_rng(0)
    reqs = []
    arrivals = [0.0, 0.0, 0.0, 0.0, 2.0, 3.0, 5.0, 8.0]
    for i, arr in enumerate(arrivals):
        reqs.append(ServeRequest(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, 4 + i % 5).astype(
                np.int32),
            max_new=4 + i % 4, arrival_s=arr))
    refs = {r.rid: greedy_reference(bundle, params, r.prompt, r.max_new, 48)
            for r in reqs}
    plan = FaultPlan([
        # kill replica 0 mid-decode: instant quarantine, in-flight work
        # fails over to replica 1 and re-decodes from the prompt
        FaultSpec(site="serve.replica", kind="device_loss",
                  when=lambda c: c["replica"] == 0 and c["tick"] == 3),
        # silently stall replica 1: only the decode-step heartbeat may
        # notice (dispatch never sees the injected state)
        FaultSpec(site="serve.replica", kind="stall", hang_s=6.0, times=1,
                  when=lambda c: c["replica"] == 1 and c["tick"] == 5),
    ], seed=0)
    rcfg = RouterConfig(replicas=2, max_queue=3, heartbeat_misses=2,
                        engine=EngineConfig(slots=2, cache_len=48, pad_to=4,
                                            max_prefill_batch=2))
    router = ReplicaRouter(bundle, params, rcfg, faults=plan)
    done = router.run(list(reqs))
    s = router.stats
    # zero silent drops: every request back exactly once, admitted+shed=all
    assert [r.rid for r in done] == list(range(len(reqs)))
    assert s["admitted"] + s["shed_queue"] + s["shed_deadline"] == len(reqs)
    # the burst of 4 over max_queue=3 shed one up front; losing half the
    # capacity mid-run backs the queue up and sheds more — all explicit
    shed = [r for r in done if r.rejected]
    assert len(shed) == s["shed_queue"] >= 1
    assert 3 not in {r.rid for r in done if not r.rejected}  # burst overflow
    assert all(not r.out and not r.done for r in shed)
    # both faults really fired
    assert plan.fired("serve.replica", kind="device_loss")
    assert plan.fired("serve.replica", kind="stall")
    # the dead replica was quarantined (and stays dead); the stalled one
    # was caught by the heartbeat, evicted and restarted
    assert s["quarantined"] == [0]
    assert not router.replicas[0].live and router.replicas[1].live
    assert s["failovers"] >= 1 and s["restarts"] >= 1
    # bit-identical parity for every admitted request: the fault-free
    # greedy reference is the oracle (failover re-decodes from the prompt,
    # greedy decode is deterministic, so partial work lost with replica 0
    # is reproduced exactly on replica 1)
    for r in done:
        if r.rejected:
            continue
        assert not r.expired
        assert r.out == refs[r.rid], r.rid


def test_router_goodput_holds_under_replica_loss():
    """Losing one of two replicas mid-run costs less than half the
    goodput: the survivor keeps its slots full.  Goodput is completed
    requests over the virtual makespan (one decode step is one virtual
    second), so the ratio is the same on any machine."""
    from repro.serve import (EngineConfig, ReplicaRouter, RouterConfig,
                             poisson_workload)
    cfg, bundle, params = _serve_setup()

    def workload():
        reqs = poisson_workload(24, vocab_size=cfg.vocab_size,
                                rate_per_s=2.0, prompt_lens=(4, 8, 12, 16),
                                out_lens=(4, 6, 8), seed=0)
        for r in reqs:
            r.deadline_s = 30.0
        return reqs

    def goodput(done):
        ok = [r for r in done if r.done and not r.expired and not r.rejected]
        assert len(ok) == len(done) == 24    # nothing shed, expired, lost
        return len(ok) / max(r.t_done for r in ok)

    rcfg = RouterConfig(replicas=2, engine=EngineConfig(
        slots=4, cache_len=64, pad_to=8, max_prefill_batch=4))
    fault_free = goodput(ReplicaRouter(bundle, params, rcfg).run(workload()))
    plan = FaultPlan([FaultSpec(site="serve.replica", kind="device_loss",
                                when=lambda c: c["replica"] == 0
                                and c["tick"] == 5)])
    router = ReplicaRouter(bundle, params, rcfg, faults=plan)
    lossy = goodput(router.run(workload()))
    assert plan.fired("serve.replica", kind="device_loss")
    assert router.stats["quarantined"] == [0]
    assert router.stats["failovers"] >= 1
    assert lossy >= 0.6 * fault_free


def test_router_dispatch_fault_redispatches():
    """A crash at the router.dispatch hand-off itself: the chosen replica
    is failed and restarted, the request is requeued, and everything still
    completes bit-identically."""
    from repro.serve import (EngineConfig, ReplicaRouter, RouterConfig,
                             ServeRequest, greedy_reference)
    cfg, bundle, params = _serve_setup()
    rng = np.random.default_rng(1)
    reqs = [ServeRequest(rid=i,
                         prompt=rng.integers(0, cfg.vocab_size, 5).astype(
                             np.int32),
                         max_new=4, arrival_s=0.0) for i in range(4)]
    refs = {r.rid: greedy_reference(bundle, params, r.prompt, r.max_new, 48)
            for r in reqs}
    plan = FaultPlan([FaultSpec(site="router.dispatch", kind="crash",
                                at=(1,))])
    router = ReplicaRouter(bundle, params, RouterConfig(
        replicas=2, engine=EngineConfig(slots=2, cache_len=48, pad_to=4,
                                        max_prefill_batch=2)), faults=plan)
    done = router.run(reqs)
    assert len(done) == 4
    for r in done:
        assert not r.rejected and not r.expired
        assert r.out == refs[r.rid]
    assert plan.fired("router.dispatch", kind="crash")
    assert router.stats["restarts"] == 1      # failed at hand-off, restarted
    assert router.stats["quarantined"] == []  # one strike, not a streak


def test_async_checkpoints_only_at_drain_barriers(tmp_path):
    """Every checkpoint an async run writes is a drained cut: fully
    trained, generation a multiple of the barrier stride."""
    path = str(tmp_path / "ckpt.json")
    seen = []
    s = _search(pipeline="async", lookahead=1, ckpt_every=2)
    orig = s.save_state

    def spy(state, p):
        seen.append(state.generation)
        assert state.pop.trained_mask.all()
        orig(state, p)

    s.save_state = spy
    s.run_resumable(path)
    assert seen[0] == 0                     # the post-init persist
    assert seen[1:] == [2, 4]               # barrier stride, then final
