"""The trace reduction: device busy time as a union of operations, time
per operation and program, idle gaps laid against host spans."""
import pytest

import trace_reduce as tr


def events():
    # window 1.0 .. 2.0 s; two ops overlap; one op sticks out of the window
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
                 ("submit", 1.40, 0.10), ("tick", 1.55, 0.6)],
    }


def test_busy_is_the_union_of_operations_inside_the_window():
    r = tr.reduce_events(events())
    # [1.0, 1.1] + [1.15, 1.3] + [1.6, 1.7] + [1.95, 2.0]
    assert r["window_s"] == pytest.approx(1.0)
    assert r["busy_s"] == pytest.approx(0.1 + 0.15 + 0.1 + 0.05)


def test_time_per_operation_kind_and_program():
    r = tr.reduce_events(events())
    assert r["ops_s"]["jit__decode/fusion"] == pytest.approx(0.1 + 0.1)
    assert r["ops_s"]["jit__decode/decode_attention"] == pytest.approx(0.1)
    assert r["programs_s"]["jit__decode"] == pytest.approx(0.3)
    assert r["programs_s"]["jit__prefill"] == pytest.approx(0.4)
    assert tr.time_matching(r["ops_s"], "decode_attention") == \
        pytest.approx(0.1)
    assert tr.time_matching(r["ops_s"], "nothing here") is None


def test_idle_gaps_name_what_the_host_was_doing():
    r = tr.reduce_events(events())
    gaps = r["breakdown"]["idle_gaps"]
    # [1.3, 1.6] is the longest, its middle 1.45 inside "submit"
    assert gaps[0][0] == "submit" and gaps[0][1] == pytest.approx(0.3)
    assert [g[0] for g in gaps][1] == "tick"            # [1.7, 1.95]
    assert sum(g[1] for g in gaps) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert len(r["breakdown"]["device_ops"]) <= tr.TOP_N


def test_a_trace_without_the_window_span_is_refused():
    ev = events()
    ev["host"] = [e for e in ev["host"] if e[0] != "bench_window"]
    with pytest.raises(ValueError, match="bench_window"):
        tr.reduce_events(ev)


def recorded():
    """17 ms of a traced chat_dense window on one TPU v5e: the end of a
    decode step, a prefill, its splice and the start of the next decode
    (instruction names as ``load_events`` gives them)."""
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_chat_trace.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_trace_programs_and_busy_time():
    ev = recorded()
    r = tr.reduce_events(ev)
    _, w0, wd = [e for e in ev["host"] if e[0] == "bench_window"][0]
    assert r["window_s"] == pytest.approx(wd)
    mods = ev["devices"]["/device:TPU:0"]["modules"]
    want = {}
    for name, s, d in mods:
        k = tr.program_name(name)
        want[k] = want.get(k, 0) + min(s + d, w0 + wd) - max(s, w0)
    assert set(r["programs_s"]) == {"jit__decode", "jit__prefill",
                                    "jit__splice"}
    for k, v in want.items():
        assert r["programs_s"][k] == pytest.approx(v)
    # the device works inside its programs, and idles between them
    assert sum(want.values()) * 0.9 < r["busy_s"] <= sum(want.values())
    assert r["busy_s"] + sum(g[1] for g in r["breakdown"]["idle_gaps"]) \
        <= r["window_s"] + 1e-9


def test_recorded_trace_kernel_and_self_times():
    ev = recorded()
    r = tr.reduce_events(ev)
    _, w0, wd = [e for e in ev["host"] if e[0] == "bench_window"][0]
    ops = ev["devices"]["/device:TPU:0"]["ops"]
    kernel = sum(min(s + d, w0 + wd) - max(s, w0) for n, s, d in ops
                 if n.startswith("decode_attention"))
    assert kernel > 0
    assert r["ops_s"]["jit__decode/decode_attention"] == pytest.approx(kernel)
    # self times add up to the busy time: nothing counted twice
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    # the longest gap is the host's work between the decode and the
    # prefill, inside a tick
    assert r["breakdown"]["idle_gaps"][0][0] == "tick"
    assert r["breakdown"]["idle_gaps"][0][1] > 1e-3


def test_instruction_names():
    assert tr.instruction("%while.13 = (s32[]) while(x), body=%b") == \
        "while.13"
    assert tr.op_kind("copy_bitcast_fusion.5") == "copy_bitcast_fusion"
    assert tr.program_name("jit__decode(5296305762024638329)") == \
        "jit__decode"


def _reader_ctx(r):
    import json
    import os
    import harness
    cfg = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "configs", "qwen2-0.5b", "config.json")
    with open(cfg) as f:
        config = json.load(f)
    return {"trace": r, "traced_decode": [[1024] * 32],
            "config": config, "peaks": harness.peaks_for("TPU v5 lite")}


@pytest.mark.parametrize("name", ["decode_attn_roofline.chat",
                                  "decode_mfu.chat", "device_idle.chat"])
def test_readers_find_their_names_in_the_recorded_trace(name):
    import harness
    value = harness.metric_reader(name)(_reader_ctx(
        tr.reduce_events(recorded())))
    assert value is not None and value > 0


def test_a_renamed_decode_program_fails_the_mfu_reader():
    import harness
    r = tr.reduce_events(recorded())
    r["programs_s"] = {k.replace("jit__decode", "jit__step"): v
                       for k, v in r["programs_s"].items()}
    with pytest.raises(harness.BenchError, match="jit__decode"):
        harness.metric_reader("decode_mfu.chat")(_reader_ctx(r))
