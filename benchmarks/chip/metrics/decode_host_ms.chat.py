"""Host milliseconds of a decode step spent on anything but waiting for
the device (the engine's ``engine.decode`` phase less its
``engine.decode_wait``: the mask, the transfers, the dispatch and the
emit loop), averaged over every decode step of the run
(``engine.stats()`` after the drain).  Layer: engine (``serve/engine.py``
``step``).  Moves ``itl_p95_ms``."""


def read(ctx):
    st = ctx.get("engine_stats") or {}
    s, n = st.get("phase_s") or {}, st.get("phase_n") or {}
    if not n.get("decode"):
        return None
    return 1e3 * (s["decode"] - s["decode_wait"]) / n["decode"]
