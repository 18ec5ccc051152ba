"""Share of the traced slice in which no operation ran on the device, in
percent.  Layer: device.  Moves ``itl_p95_ms``."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
