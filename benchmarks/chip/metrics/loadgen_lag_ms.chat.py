"""95th percentile of how late the harness submitted each request
against its schedule, in milliseconds.  Layer: load generator (the
harness).  Moves ``ttft_p95_ms``: a late generator shows here, not as a
slow engine."""
import harness


def read(ctx):
    lag = ctx.get("submit_lag_s")
    if not lag:
        return None
    return 1e3 * harness.percentile(lag, 95)
