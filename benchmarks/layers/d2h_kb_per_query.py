"""Device: what the ``device-sync`` stages brought from the device to the
host in the window, in KB (1,000 bytes) per answered query
(``filodb_device_to_host_bytes_total``). ``None`` on a program that does not
count it."""


def read(ctx):
    if not ctx.ok or "filodb_device_to_host_bytes_total" not in ctx.m1:
        return None
    return ctx.delta("filodb_device_to_host_bytes_total") / 1e3 / len(ctx.ok)
