"""Device: self time of device-sync (waiting for results to reach the host) per
answered query.
Stages and families: ``stages.py``."""

import stages


def read(ctx):
    return stages.self_ms(ctx, "device_wait_ms")
