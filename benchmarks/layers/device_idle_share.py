"""Device: 1 - the union of the device's operation intervals over the traced
window (averaged over the chips used)."""


def read(ctx):
    t = ctx.trace
    if not t or t.get("busy_s") is None or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
