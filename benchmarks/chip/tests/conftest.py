import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

TINY = os.path.join(HERE, "tiny")
# each test-sized serving cell stands in for a real cell and reports the
# real cell's metrics
TINY_CELLS = {"tiny-qwen2.tiny_chat": "qwen2-0.5b.chat_dense"}
TINY_CELL = "tiny-qwen2.tiny_chat"


@pytest.fixture
def tiny_root(tmp_path):
    """A BENCHMARK.json with the real metrics and the test-sized serving
    cells; their configuration, traffic and limits are under tests/tiny."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the test-sized configuration, with the real plain reference beside it
    cdir = tmp_path / "configs" / "tiny-qwen2"
    cdir.mkdir(parents=True)
    shutil.copy(os.path.join(TINY, "config.json"), cdir / "config.json")
    shutil.copy(os.path.join(BENCH, "configs", "qwen2-0.5b", "reference.py"),
                cdir / "reference.py")
    bench["configs"] = [{"name": "tiny-qwen2",
                         "source": "https://huggingface.co/Qwen/Qwen2-0.5B",
                         "file": str(cdir / "config.json"),
                         "reduced": [], "why": "CPU rehearsal"}]
    bench["workloads"] = [{"name": tiny, "config": "tiny-qwen2",
                           "traffic": tiny.split(".")[1], "chips": 1,
                           "why": "CPU rehearsal"} for tiny in TINY_CELLS]
    for key in ("end_to_end", "per_layer"):
        for m in bench[key]:
            if "workloads" in m:
                m["workloads"] = [t for t, real in TINY_CELLS.items()
                                  if real in m["workloads"]]
        bench[key] = [m for m in bench[key] if m.get("workloads", True)]
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return str(tmp_path)


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU has no entry in the peaks table; tests stand one in."""
    import harness
    monkeypatch.setattr(harness, "peaks_for", lambda kind, path=None: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
        "hbm_bytes": 1e9})
