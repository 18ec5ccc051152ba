"""Host milliseconds of a tick that admitted nothing (a decode step
alone, host round trip included), averaged over the window's ticks.
Layer: engine (``serve/engine.py``).  Moves ``itl_p95_ms``."""


def read(ctx):
    ticks = [t["dur"] for t in ctx.get("ticks", [])
             if t["admitted"] == 0 and t["produced"] > 0]
    if not ticks:
        return None
    return 1e3 * sum(ticks) / len(ticks)
