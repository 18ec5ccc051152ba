"""Share of the dense engine's cache updates (each decode step and each
prefill splice) that consumed the cache donated to them, so that XLA
updated it in place instead of writing a new one, in percent, over the run
(``engine.stats()`` after the drain).  Layer: model step
(``models/transformer.py`` ``lm_decode_step_slotted`` and the engine's
``jax.jit`` around it and around the splice).  Moves ``itl_p95_ms``."""


def read(ctx):
    st = ctx.get("engine_stats") or {}
    n = st.get("cache_updates")
    if not n:
        return None
    return 100.0 * st["cache_inplace"] / n
