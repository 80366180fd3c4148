"""Backend dispatch: device buffers that calls of cached executables made
from host values in the window (``filodb_host_to_device_puts_total``: an
argument that is a numpy array or scalar or a Python number counts once for
each device the executable runs on, so one replicated over four chips counts
four), per answered query. The fused programs hand the device one host array
a request, their grid: 1 on one chip, 4 on the four-chip mesh. ``None`` on a
program that does not count it."""


def read(ctx):
    if not ctx.ok or "filodb_host_to_device_puts_total" not in ctx.m1:
        return None
    return ctx.delta("filodb_host_to_device_puts_total") / len(ctx.ok)
