"""Share of the traced slice in which the device idled under one of the
engine's phase spans (``engine.*``, the innermost host span over the idle
time), in percent.  What is left of ``device_idle.chat`` lies under the
harness's own spans or none.  The trace the run reduced is read again with
the engine's spans (``engine_spans.py``), and the engine-aware idle
breakdown goes to standard error.  Layer: device.  Moves ``itl_p95_ms``.
A program without the engine's spans leaves the metric out."""
import engine_spans
import harness


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    path = engine_spans.newest_trace(harness.TRACE_DIR)
    if path is None:
        return None
    red = engine_spans.reduce_events(engine_spans.load_events(path))
    if abs(red["window_s"] - tr["window_s"]) > 1e-9:
        harness.log(f"[engine spans] {path} is not the reduced trace "
                    f"(window {red['window_s']!r} s, not "
                    f"{tr['window_s']!r} s)")
        return None
    by_span = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])
    harness.log(f"[engine spans] idle seconds by innermost span {by_span}; "
                f"longest idle gaps {red['idle_gaps']}")
    idle = engine_spans.engine_idle_s(red)
    return None if idle is None else 100.0 * idle / red["window_s"]
