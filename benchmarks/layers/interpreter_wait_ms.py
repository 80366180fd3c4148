"""HTTP + parse/plan + engine: how late the program's probe got the
interpreter back, mean over the window's probes
(``filodb_interpreter_wait_seconds`` sum over count; ``interpreter_busy_share``
says what the probe is): the price of ONE hand-back of a request thread at
this load, the kernel's own wake-up latency included (0.6 ms on an idle node of
the chip's host: read a loaded node against that). A stage's wall less CPU is
this times its hand-backs. It is a mean over 4-5 thousand probes, so the few
that a stall of the whole process makes 0.1-0.25 s late add 0.1-0.3 ms to it;
the histogram's buckets have them, which no reader can take yet. ``None`` on a
program without the probe."""


def read(ctx):
    n = ctx.delta("filodb_interpreter_wait_seconds_count")
    if n <= 0:
        return None
    return ctx.delta("filodb_interpreter_wait_seconds_sum") / n * 1e3
