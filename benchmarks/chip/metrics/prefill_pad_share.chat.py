"""Share of the prefilled tokens that are padding, in percent: 1 less the
real prompt tokens over the padded rows times padded length of every
prefill bucket dispatched (pad rows included), over the run
(``engine.stats()`` after the drain).  Layer: engine (``serve/buckets.py``,
``serve/engine.py`` ``_admit``).  Moves ``ttft_p95_ms``."""


def read(ctx):
    st = ctx.get("engine_stats") or {}
    padded = st.get("prefill_padded_tokens")
    if not padded:
        return None
    return 100.0 * (1.0 - st["prefill_tokens"] / padded)
