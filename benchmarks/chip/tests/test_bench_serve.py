"""CPU rehearsal of a serving cell at test size: the whole run but the
look for a chip, the result line's schema, the window's arithmetic, the
control, and a fault planted where tokens are produced."""
import json
import os
import subprocess
import sys
import time
import types

import pytest

import harness
import loadgen
import run
import serve_cell
from conftest import BENCH, ROOT, TINY, TINY_CELL, TINY_CELLS


def run_tiny(root, cell=TINY_CELL, seed=2**31 + 11, seconds=2.0):
    import jax
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])
    return run.run_cell(args, devices=jax.devices(), t_start=time.monotonic(),
                        root=root, bench_dir=TINY)


@pytest.fixture(autouse=True)
def cache_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "jax"))


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_rehearsal_prints_the_result_schema(tiny_root, cpu_peaks, cell):
    line = run_tiny(tiny_root, cell)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True
    assert line["attempted"] == 16 and line["failed"] == 0
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        want = {m["name"] for m in json.load(f)["end_to_end"]
                if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["count"] == 1
    assert set(line["compared"]) == {"max_logit_gap", "nonfinite_rows",
                                     "unanswered"}
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)


def test_a_token_altered_where_produced_fails(tiny_root, cpu_peaks,
                                              monkeypatch):
    from repro.serve.engine import ServeEngine
    step = ServeEngine.step

    def altered(self, now):
        produced = step(self, now)
        for s, req in enumerate(self.active):
            if req is not None and len(req.out) == 3:
                req.out[-1] = (req.out[-1] + 1) % 512
                self.last_tok[s] = req.out[-1]
        return produced
    monkeypatch.setattr(ServeEngine, "step", altered)
    line = run_tiny(tiny_root)
    assert line["correct"] is False
    gap = line["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_the_control_reads_above_the_limit(tiny_root, cpu_peaks):
    import calibrate
    cell = harness.find_cell(TINY_CELL, root=tiny_root, bench_dir=TINY)
    harness.enable_compile_cache()
    r = calibrate.readings(cell, 3, 2.0)
    limit = cell.limits["max_logit_gap"]["limit"]
    assert r["program"]["max"] <= limit < r["control"]["max"]
    assert r["program"]["tokens"] == r["control"]["tokens"] > 0


def test_the_control_served_in_the_programs_place_is_not_correct(
        tiny_root, cpu_peaks, monkeypatch):
    """Every token the engine emits is replaced by the one the control
    (the reference in float8) puts first after the same sequence, and
    the whole run goes through the harness's own comparison."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.engine import ServeEngine
    cell = harness.find_cell(TINY_CELL, root=tiny_root, bench_dir=TINY)
    length = int(cell.traffic["engine"]["cache_len"])
    _, control_first = serve_cell.load_reference(cell).make_scorer(
        cell.config)

    def control_token(engine, req):
        toks = list(req.prompt) + list(req.out[:-1])
        seq = np.zeros(length, np.int32)
        seq[:len(toks)] = toks
        return int(np.asarray(control_first(engine.params,
                                            jnp.asarray(seq)))[len(toks) - 1])

    def redo(engine, pairs):
        for slot, req in pairs:
            req.out[-1] = control_token(engine, req)
            if engine.active[slot] is req:
                engine.last_tok[slot] = req.out[-1]

    admit, step = ServeEngine._admit, ServeEngine.step

    def admit_control(self, now):
        waiting = list(self.waiting)
        n = admit(self, now)
        pairs = []
        for req in waiting[:n]:
            slot = next((s for s, r in enumerate(self.active) if r is req),
                        -1)
            pairs.append((slot, req))
        redo(self, pairs)
        return n

    def step_control(self, now):
        pairs = [(s, r) for s, r in enumerate(self.active) if r is not None]
        produced = step(self, now)
        redo(self, pairs)
        return produced

    monkeypatch.setattr(ServeEngine, "_admit", admit_control)
    monkeypatch.setattr(ServeEngine, "step", step_control)
    line = run_tiny(tiny_root)
    assert line["attempted"] == 16 and line["failed"] == 0
    assert line["correct"] is False
    gap = line["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def _served(arrival, stamps, max_new):
    req = types.SimpleNamespace(done=True, out=[0] * len(stamps),
                                max_new=max_new, oom=False, expired=False,
                                rejected=False)
    plan = types.SimpleNamespace(arrival_s=arrival)
    return serve_cell.Served(planned=plan, req=req, submit_s=arrival + 0.001,
                             stamps=stamps)


def test_window_arithmetic_counts_every_sample():
    wlog = serve_cell.WindowLog(served=[
        _served(0.1, [0.3, 0.3, 0.5, 0.9], 4),
        _served(0.5, [0.7, 1.2, 1.4], 3),       # last tokens after close
        _served(0.8, [1.5], 2),                 # never finished
    ], seconds=1.0, ticks=[], traced_decode=[])
    m = serve_cell.window_metrics(wlog)
    assert m["attempted"] == 3 and m["failed"] == 1
    assert len(m["ttft_s"]) == 3 and len(m["itl_s"]) == 3 + 2
    assert m["out_tokens_in_window"] == 4 + 1
    assert m["out_tok_per_s"] == 5.0
    assert sorted(round(x, 6) for x in m["ttft_s"]) == [0.2, 0.2, 0.7]
    assert min(m["itl_s"]) == 0.0
    assert harness.percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)


def test_prefill_shapes_cover_the_traffic():
    spec = {"pad_to": 128, "max_prefill_batch": 8}
    shapes = serve_cell.prefill_shapes(spec, [100, 128, 129, 1792, 300])
    assert shapes == [(b, n) for n in (128, 256, 384, 1792)
                      for b in (1, 2, 4, 8)]
    t = {"arrivals": {"process": "poisson", "rate_per_s": 4.0},
         "prompt_len": {"dist": "lognormal", "median": 1020, "sigma": 0.5,
                        "min": 64, "max": 3072},
         "output_len": {"dist": "lognormal", "median": 129, "sigma": 1.0,
                        "min": 8, "max": 1024}}
    for seed in (1, 2**31 + 5):
        plan = loadgen.schedule(t, seed, 50.0, vocab_size=100)
        want = {(8, -(-len(p.prompt) // 128) * 128) for p in plan}
        assert want <= set(serve_cell.prefill_shapes(spec, [
            len(p.prompt) for p in plan]))


def _run_script(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", "qwen2-0.5b.chat_dense", "--seed", "0", "--seconds",
         "10", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _printed_a_result(stdout):
    return any(line.strip().startswith("{") for line in stdout.splitlines())


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    p = _run_script(ROOT)
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)
    assert "no TPU" in p.stderr


def test_the_benchmark_alone_cannot_run(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run_script(str(tmp_path))
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)
