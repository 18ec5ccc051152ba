"""Host milliseconds of an admitting round (the engine's ``engine.admit``
phase: bucketing, each bucket's prefill and splice dispatch, the wait for
its first tokens, and placing the rows), averaged over every admitting
round of the run (``engine.stats()`` after the drain).  Layer: engine
(``serve/engine.py`` ``_admit``).  Moves ``ttft_p95_ms``."""


def read(ctx):
    st = ctx.get("engine_stats") or {}
    n = (st.get("phase_n") or {}).get("admit")
    if not n:
        return None
    return 1e3 * st["phase_s"]["admit"] / n
