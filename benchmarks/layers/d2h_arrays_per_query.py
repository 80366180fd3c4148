"""Device: arrays the ``device-sync`` stages brought from the device to the
host in the window (``filodb_device_to_host_arrays_total``, one a transfer),
per answered query. The fused programs return their whole answer as one
array: 1 where every request takes one of them. ``None`` on a program that
does not count it."""


def read(ctx):
    if not ctx.ok or "filodb_device_to_host_arrays_total" not in ctx.m1:
        return None
    return ctx.delta("filodb_device_to_host_arrays_total") / len(ctx.ok)
