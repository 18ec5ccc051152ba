"""Device idle time laid against the engine's phase spans
(``engine_spans.py``), and the four readers of the engine's spans and
counters: each on a hand-made context, and each silent without its
input, as it is for a program that has no engine spans or counters."""
import gzip
import json
import os

import pytest

import engine_spans as es
import harness
import trace_reduce as tr


def events():
    """Window 1.0 .. 2.0 s; busy [1.0, 1.1], [1.15, 1.3], [1.6, 1.7],
    [1.95, 2.0].  Idle under each innermost span: [1.1, 1.15] under
    ``engine.decode_wait``; [1.3, 1.6] under ``engine.tick`` 0.04,
    ``tick`` 0.01, none 0.05, ``submit`` 0.1, none 0.05, ``tick`` 0.01,
    ``engine.tick`` 0.02 and ``engine.admit`` 0.02; [1.7, 1.95] under
    ``engine.emit`` 0.15, ``engine.decode`` 0.04 and ``engine.tick``
    0.06."""
    return {
        "devices": {"/device:TPU:0": {
            "ops": [("fusion.12", 0.9, 0.2),
                    ("decode_attention.1", 1.15, 0.10),
                    ("fusion.7", 1.20, 0.10),
                    ("convolution.3", 1.6, 0.1),
                    ("fusion.2", 1.95, 0.2)],
            "modules": [("jit__decode(3)", 0.9, 0.4),
                        ("jit__prefill(4)", 1.6, 0.5)]}},
        "host": [("bench_window", 1.0, 1.0), ("tick", 0.95, 0.4),
                 ("submit", 1.40, 0.10), ("tick", 1.55, 0.6),
                 ("engine.tick", 0.96, 0.38), ("engine.decode", 0.97, 0.3),
                 ("engine.decode_wait", 0.98, 0.2),
                 ("engine.tick", 1.56, 0.58), ("engine.admit", 1.58, 0.1),
                 ("engine.decode", 1.69, 0.2), ("engine.emit", 1.7, 0.15)],
    }


def recorded():
    """The recorded v5e trace of a program without engine spans."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_chat_trace.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_idle_gaps_name_the_innermost_engine_phase():
    gaps = es.reduce_events(events())["idle_gaps"]
    assert gaps[0][0] == "submit" and gaps[0][1] == pytest.approx(0.3)
    # the middle of [1.7, 1.95] is inside engine.emit, inside the tick
    assert gaps[1][0] == "engine.emit" and gaps[1][1] == pytest.approx(0.25)
    assert gaps[2][0] == "engine.decode_wait"


def test_idle_seconds_by_span_sum_to_the_idle_time():
    r = es.reduce_events(events())
    want = {"engine.decode_wait": 0.05, "engine.tick": 0.12, "tick": 0.02,
            "none": 0.1, "submit": 0.1, "engine.admit": 0.02,
            "engine.emit": 0.15, "engine.decode": 0.04}
    assert r["idle_by_span"] == pytest.approx(want)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert es.engine_idle_s(r) == pytest.approx(0.38)
    # a span over no idle time still has its entry
    ev = events()
    ev["host"].append(("engine.prefill", 1.62, 0.03))
    assert es.reduce_events(ev)["idle_by_span"]["engine.prefill"] == 0.0


def test_idle_by_span_averages_over_devices():
    ev = events()
    ev["host"] = [e for e in ev["host"] if e[0] in ("bench_window",
                                                    "engine.emit")]
    ev["devices"]["/device:TPU:1"] = {"ops": [("fusion.1", 1.0, 1.0)],
                                      "modules": [("jit__decode(3)", 1.0,
                                                   1.0)]}
    r = es.reduce_events(ev)
    idle = r["window_s"] - r["busy_s"]
    assert r["idle_by_span"]["engine.emit"] == pytest.approx(0.15 / 2)
    assert r["idle_by_span"]["none"] == pytest.approx(idle - 0.15 / 2)


def test_window_and_busy_time_are_the_reductions_own():
    for ev in (events(), recorded()):
        r, want = es.reduce_events(ev), tr.reduce_events(ev)
        assert r["window_s"] == want["window_s"]
        assert r["busy_s"] == want["busy_s"]
    # without engine spans: the harness's spans alone, and no engine time
    r = es.reduce_events(recorded())
    assert set(r["idle_by_span"]) == {"tick", "submit"}
    assert r["idle_gaps"] == tr.reduce_events(recorded())["breakdown"][
        "idle_gaps"]
    assert es.engine_idle_s(r) is None


def test_newest_trace(tmp_path):
    assert es.newest_trace(str(tmp_path)) is None
    for i, cell in enumerate(("a.old", "b.new")):
        path = tmp_path / cell / "plugins" / "profile" / "t"
        path.mkdir(parents=True)
        (path / "host.xplane.pb").write_bytes(b"")
        os.utime(path / "host.xplane.pb", (1e9 + i, 1e9 + i))
    assert es.newest_trace(str(tmp_path)).endswith(
        os.path.join("b.new", "plugins", "profile", "t", "host.xplane.pb"))


STATS = {"decode_steps": 40, "prefill_calls": 4, "peak_concurrency": 9,
         "shed_blocks": 0, "nonfinite_rows": 0}
PHASES = {"prefill_tokens": 3000, "prefill_padded_tokens": 3200,
          "phase_s": {"tick": 1.7, "admit": 0.04, "prefill": 0.01,
                      "prefill_wait": 0.02, "decode": 1.6,
                      "decode_wait": 1.5, "emit": 0.02},
          "phase_n": {"tick": 40, "admit": 4, "prefill": 4,
                      "prefill_wait": 4, "decode": 40, "decode_wait": 40,
                      "emit": 40}}


@pytest.fixture
def trace_of(monkeypatch):
    """Stand ``ev`` in for the trace file a traced run left behind."""
    def use(ev):
        monkeypatch.setattr(es, "newest_trace", lambda root: "x.xplane.pb")
        monkeypatch.setattr(es, "load_events", lambda path: ev)
        return {"trace": tr.reduce_events(ev)}
    return use


@pytest.mark.parametrize("name,want", [("admit_ms.chat", 10.0),
                                       ("decode_host_ms.chat", 2.5),
                                       ("prefill_pad_share.chat", 6.25)])
def test_counter_readers_on_a_hand_made_context(name, want):
    ctx = {"engine_stats": {**STATS, **PHASES}}
    assert harness.metric_reader(name)(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", ["admit_ms.chat", "decode_host_ms.chat",
                                  "prefill_pad_share.chat"])
def test_counter_readers_are_silent_without_their_input(name):
    read = harness.metric_reader(name)
    assert read({}) is None
    assert read({"engine_stats": STATS}) is None       # no engine counters
    idle = {**STATS, **PHASES, "prefill_padded_tokens": 0,
            "phase_n": dict.fromkeys(PHASES["phase_n"], 0)}
    assert read({"engine_stats": idle}) is None         # nothing ran


def test_engine_idle_reader(trace_of):
    read = harness.metric_reader("device_idle_engine.chat")
    assert read(trace_of(events())) == pytest.approx(38.0)
    assert read(trace_of(recorded())) is None            # no engine spans
    assert read({}) is None
    # a trace file other than the one the run reduced is not read
    ctx = trace_of(events())
    ctx["trace"] = dict(ctx["trace"], window_s=0.5)
    assert read(ctx) is None


def test_engine_idle_reader_without_a_trace_file(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    read = harness.metric_reader("device_idle_engine.chat")
    assert read({"trace": tr.reduce_events(events())}) is None
