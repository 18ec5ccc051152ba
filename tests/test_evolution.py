"""Evolutionary NAS loop with a mock trainer: selection + dormant-gene cache."""
import numpy as np
import pytest

from repro.core.evolution import EvolutionarySearch, NASConfig
from repro.core.objectives import cheap_matrix
from repro.core.pareto import pareto_front
from repro.core.selection import (
    inverse_density_weights,
    preselect_children,
    sample_parents,
)
from repro.core.trainer import TrainResult


def mock_trainer(calls):
    def train(g):
        calls.append(g.phenotype_hash())
        det = min(0.99, 0.75 + 0.04 * g.depth())
        return TrainResult(detection_rate=det,
                           false_alarm_rate=max(0.0, 0.25 - 0.03 * g.depth()),
                           val_loss=0.3, steps=0)
    return train


def make_search(calls, **kw):
    cfg = NASConfig(generations=4, children_per_gen=8, n_accept=4,
                    init_population=6, n_workers=2, seed=0, **kw)
    return EvolutionarySearch(cfg, None, None, train_fn=mock_trainer(calls),
                              log=lambda *_: None)


def test_search_progresses_and_respects_capacity():
    calls = []
    s = make_search(calls)
    state = s.run()
    assert state.generation == 4
    assert len(state.population) <= s.cfg.population_cap
    objs = np.stack([c.objective_vector() for c in state.population])
    assert len(pareto_front(objs)) >= 1
    assert len(state.history) == 4


def test_dormant_gene_cache_prevents_retraining():
    calls = []
    s = make_search(calls)
    state = s.run()
    # every phenotype hash is trained at most once
    assert len(calls) == len(set(calls))
    assert set(calls) <= set(state.evaluated_hashes)


def test_solution_selection_honours_constraints():
    calls = []
    s = make_search(calls)
    state = s.run()
    sol = s.select_solution(state, "energy_max_alpha_j")
    if sol is not None:
        assert sol.meets_constraints(s.cfg.det_min, s.cfg.fa_max)


def test_soa_state_is_self_consistent():
    """The resident arrays must agree with what a fresh recompute (cheap
    objectives, phenotype hashes) and the object view say."""
    calls = []
    s = make_search(calls)
    state = s.init_state()
    for _ in range(2):
        state = s.step(state)
    pop = state.pop
    np.testing.assert_array_equal(
        pop.cheap, s.backend.evaluate_batch(pop.enc, space=s.space))
    assert list(pop.phash) == pop.enc.batch_phenotype_hash(s.space)
    assert len(set(pop.phash)) == len(pop)  # dedup invariant
    # object view mirrors the arrays
    for i, c in enumerate(state.population):
        assert c.phash == pop.phash[i]
        np.testing.assert_array_equal(c.cheap, pop.cheap[i])
        if c.expensive is None:
            assert not pop.trained_mask[i]
        else:
            np.testing.assert_array_equal(c.expensive, pop.expensive[i])
    # trained members are all in the dormant-gene cache
    for h in pop.phash[pop.trained_mask]:
        assert h in state.evaluated_hashes


def test_kde_weights_prefer_sparse_regions():
    # dense cluster at origin + one isolated point: the isolated point must
    # receive the largest parent-sampling weight
    pts = np.vstack([np.random.default_rng(0).normal(0, 0.01, (20, 3)),
                     np.array([[10.0, 10.0, 10.0]])])
    w = inverse_density_weights(pts)
    assert np.argmax(w) == 20
    assert np.isclose(w.sum(), 1.0)


def test_preselection_size_and_bounds():
    rng = np.random.default_rng(0)
    pop = rng.normal(size=(12, 4))
    children = rng.normal(size=(30, 4))
    idx = preselect_children(rng, pop, children, 10)
    assert len(idx) == 10 and len(set(idx.tolist())) == 10
    assert idx.max() < 30
    few = preselect_children(rng, pop, children[:5], 10)
    assert len(few) == 5


# ---------------------------------------------------------------------------
# Pipelined generation loop (DESIGN.md §11)
# ---------------------------------------------------------------------------

def _det_batch_trainer(calls=None):
    """Deterministic genome-dependent batch trainer (accepts the worker's
    device like the real bucketed path)."""
    def train(genomes, device=None):
        if calls is not None:
            calls.append([g.phenotype_hash() for g in genomes])
        out = []
        for g in genomes:
            det = min(0.99, 0.70 + 0.05 * g.depth())
            out.append(TrainResult(detection_rate=det,
                                   false_alarm_rate=max(0.0,
                                                        0.3 - 0.04 * g.depth()),
                                   val_loss=0.2, steps=0))
        return out
    return train


def pipeline_search(pipeline, seed=3, calls=None, **kw):
    cfg = NASConfig(generations=4, children_per_gen=10, n_accept=4,
                    init_population=8, population_cap=16, n_workers=2,
                    seed=seed, pipeline=pipeline, **kw)
    return EvolutionarySearch(cfg, None, None,
                              batch_train_fn=_det_batch_trainer(calls),
                              log=lambda *_: None)


def test_host_overlap_trajectory_is_bit_identical_to_off():
    """The determinism contract: ``pipeline="host_overlap"`` only overlaps
    order-independent host folds with device dispatches, so on a fixed seed
    its whole trajectory — survivors, objectives, history — equals the
    synchronous loop's bit for bit."""
    a = pipeline_search("off").run()
    b = pipeline_search("host_overlap").run()
    assert a.generation == b.generation
    assert list(a.pop.phash) == list(b.pop.phash)
    np.testing.assert_array_equal(a.pop.cheap, b.pop.cheap)
    np.testing.assert_array_equal(a.pop.expensive, b.pop.expensive)
    np.testing.assert_array_equal(a.pop.born, b.pop.born)
    assert set(a.evaluated_hashes) == set(b.evaluated_hashes)
    for ra, rb in zip(a.history, b.history):
        for k in ("generation", "children", "trained", "population",
                  "front_size", "feasible", "best_primary"):
            assert ra[k] == rb[k] or (
                np.isnan(ra[k]) and np.isnan(rb[k])), k


class _CountingBackend:
    """Counts ``evaluate_batch`` calls of the backend it wraps."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def evaluate_batch(self, enc, *, space):
        self.calls += 1
        return self.inner.evaluate_batch(enc, space=space)


@pytest.mark.parametrize("pipeline", ["off", "host_overlap"])
def test_step_scores_cheap_objectives_once(pipeline):
    """A generation step makes exactly one cheap-objective batch call, for
    its children: the population's matrix is kept on the SoA state, never
    recomputed."""
    s = pipeline_search(pipeline)
    state = s.init_state()
    s.backend = _CountingBackend(s.backend)
    for _ in range(3):
        s.backend.calls = 0
        state = s.step(state)
        assert state.history[-1]["children"] > 0
        assert s.backend.calls == 1


def test_async_pipeline_completes_and_keeps_invariants():
    """``pipeline="async"`` relaxes the trajectory but not the structural
    invariants: every generation folds, the population stays deduped and
    fully trained, and each record is tagged with the mode."""
    calls = []
    s = pipeline_search("async", calls=calls, lookahead=2)
    state = s.run()
    assert state.generation == 4
    assert len(state.history) == 4
    assert all(r.get("pipeline") == "async" for r in state.history)
    assert len(set(state.pop.phash)) == len(state.pop)
    assert state.pop.trained_mask.all()
    # the dormant-gene cache never trained a phenotype twice
    flat = [h for bucket in calls for h in bucket]
    assert len(flat) == len(set(flat))


def test_history_records_timing_breakdown():
    """Each generation records its wall-time split and the per-device busy
    time of its training jobs — the observability surface the pipeline
    benchmark (and CI gate) reads."""
    state = pipeline_search("off").run()
    for rec in state.history:
        t = rec["timings"]
        assert set(t) == {"children", "cheap_score", "train", "select"}
        assert all(v >= 0.0 for v in t.values())
        assert isinstance(rec["device_busy_s"], dict)
        assert rec["train_jobs"] >= 0
    trained_recs = [r for r in state.history if r["trained"]]
    assert any(r["train_jobs"] > 0 for r in trained_recs)
    assert any(r["device_busy_s"] for r in trained_recs)


def test_failed_candidates_get_schema_derived_pessimism():
    """A candidate whose training fails permanently lands at the schema's
    worst-case expensive row (not a hard-coded 2-vector)."""
    from repro.core.objective_schema import pessimistic_expensive

    def explode(g):
        raise RuntimeError("bucket OOM")

    cfg = NASConfig(generations=1, children_per_gen=4, n_accept=2,
                    init_population=4, n_workers=2, seed=0)
    s = EvolutionarySearch(cfg, None, None, train_fn=explode,
                           log=lambda *_: None)
    s.scheduler.max_retries = 0
    state = s.init_state()
    worst = pessimistic_expensive(s.full_schema)
    assert state.pop.expensive.shape[1] == len(worst)
    np.testing.assert_array_equal(
        state.pop.expensive, np.tile(worst, (len(state.pop), 1)))


def test_unknown_pipeline_mode_rejected():
    import pytest
    cfg = NASConfig(pipeline="sometimes")
    with pytest.raises(ValueError, match="pipeline"):
        EvolutionarySearch(cfg, None, None, train_fn=lambda g: None,
                           log=lambda *_: None)


def test_async_pipeline_checkpoints_at_drain_barriers(tmp_path):
    """``run_resumable`` under ``pipeline='async'`` checkpoints at drain
    barriers (DESIGN.md §13) instead of rejecting: the run completes to
    target and leaves a loadable, fully-trained checkpoint behind."""
    path = str(tmp_path / "ckpt.json")
    s = pipeline_search("async")
    final = s.run_resumable(path)
    assert final.generation == 4
    restored = pipeline_search("async").load_state(path)
    assert restored.generation == 4
    assert list(restored.pop.phash) == list(final.pop.phash)
    np.testing.assert_array_equal(restored.pop.expensive,
                                  final.pop.expensive)
    assert restored.pop.trained_mask.all()


def test_device_imbalance_helper():
    from repro.core.evolution import device_imbalance
    # meaningless cases: <2 devices, or a generation with ~no device work
    assert device_imbalance({}) is None
    assert device_imbalance({"cpu:0": 5.0}) is None
    assert device_imbalance({"cpu:0": 0.0, "cpu:1": 0.0}) is None
    # balanced vs skewed
    assert abs(device_imbalance({"cpu:0": 1.0, "cpu:1": 1.1}) - 1.1) < 1e-9
    assert abs(device_imbalance({"cpu:0": 1.0, "cpu:1": 4.0,
                                 "cpu:2": 2.0}) - 4.0) < 1e-9
    # one device idle while others trained: worst possible skew
    assert device_imbalance({"cpu:0": 0.0, "cpu:1": 3.0}) == float("inf")


def test_device_imbalance_warning_logged():
    """A skewed generation surfaces a scheduler warning and records the
    ratio in the history (device-affine sharding can pin the big signature
    buckets to one device — the log line is the operator's signal)."""
    from repro.core.evolution import DEVICE_IMBALANCE_RATIO

    def run_with_busy(busy):
        lines = []
        cfg = NASConfig(generations=1, children_per_gen=6, n_accept=2,
                        init_population=6, n_workers=2, seed=0)
        s = EvolutionarySearch(cfg, None, None, train_fn=mock_trainer([]),
                               log=lambda *a: lines.append(
                                   " ".join(str(x) for x in a)))
        state = s.init_state()
        orig = s._finish_training
        # train for real, but report the synthetic per-device busy split
        s._finish_training = lambda *a, **k: (orig(*a, **k), busy)[1]
        state = s.step(state)
        return lines, state

    lines, state = run_with_busy({"cpu:0": 0.1, "cpu:1": 1.0})
    assert any("WARNING" in ln and "imbalance 10.0x" in ln for ln in lines)
    rec = state.history[-1]
    assert abs(rec["device_imbalance"] - 10.0) < 1e-6
    assert rec["device_imbalance"] > DEVICE_IMBALANCE_RATIO

    lines, state = run_with_busy({"cpu:0": 1.0, "cpu:1": 1.1})
    assert not any("imbalance" in ln for ln in lines)
    assert "device_imbalance" not in state.history[-1]
