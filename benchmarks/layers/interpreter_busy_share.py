"""HTTP + parse/plan + engine: the share of the window in which a thread that
asked for the interpreter waited more than 2.5 ms for it, as the program's
probe saw it: a lower bound of the share in which somebody else held it. A
daemon thread of the node (``filodb_tpu/obs/process.py`` ``InterpreterProbe``)
sleeps 5-15 ms, drawn uniformly, and on waking takes how late it was; a probe
more than 2.5 ms late waited for the interpreter
(``filodb_interpreter_probes_waited_total``) and every probe is in
``filodb_interpreter_wait_seconds_count``. The arrivals are random in time, so
waited over count estimates the share of time, to about +-1.4 points over a
window's 5,100 probes. The line stands above what a
sleeper's wake-up costs on the chip's host with nobody holding the interpreter
(0.6 ms at the mean), so an idle node reads 0.4-1.1, the host's own late
wake-ups, and a loaded node is read against that floor. 0 is a reading;
``None`` on a program without the probe."""


def read(ctx):
    n = ctx.delta("filodb_interpreter_wait_seconds_count")
    if n <= 0:
        return None
    return 100.0 * ctx.delta("filodb_interpreter_probes_waited_total") / n
