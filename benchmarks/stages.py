"""Where a query's time goes inside the node: the program's stage counters.

The program times every layer boundary of the served path with one stage span
(``filodb_tpu/obs/trace.py``, ``STAGES``) and exports, per stage ``S`` (hyphens
as underscores), three unlabelled counter families on ``/metrics``, tracer and
profiler on or off:

    filodb_stage_<S>_calls_total          spans closed
    filodb_stage_<S>_self_seconds_total   wall seconds less child stages
    filodb_stage_<S>_cpu_seconds_total    thread CPU seconds over that self time

Self time is exclusive, so over the request threads the stages under ``query``
add up to ``filodb_query_latency_seconds_sum`` (what ``server_query_ms``
reads); ``admission-wait`` is taken before ``query`` opens and comes on top.
A stage that the batcher's executor thread runs for a parked leader is a child
of that leader's ``batcher-queue-wait`` (wall only), so nothing counts twice.
Wall minus CPU of a stage is time it waited: for the GIL, a lock, the device.
The CPU family is a sampled estimate (the thread CPU clock is a system call:
the program reads it for one request tree per 100 ms and root stage, weighted
by the trees skipped), so read it over a window of hundreds of queries or of
requests slower than 100 ms, which are all read.

``ROWS`` groups the query-path stages into the per-layer metrics of
BENCHMARK.json; every query-path stage is in exactly one row. ``WRITE_PATH``
lists the stages of the write and set-up path, which no metric reads yet (no
admitted cell writes). A reader under ``layers/`` is

    import stages
    def read(ctx):
        return stages.self_ms(ctx, "select_ms")

``self_ms`` is the row's self seconds in the window over the queries the node
answered in it (``filodb_query_latency_seconds_count``), in ms; ``None`` where
either is 0, as on a program without these counters, so the metric is left
out of the line. ``cpu_share`` is CPU over self seconds, in percent, over every
row's stages but the waits (``WAITS``).
"""

ROWS = {
    "parse_plan_ms": ("parse", "plan", "resultcache-stitch"),
    "select_ms": ("select-series", "select-span", "group-keys"),
    "dispatch_host_ms": ("tile-entry", "tile-build", "fused-eligibility",
                         "onehot", "pack", "device-eval", "device-dispatch",
                         "kernel-build", "aggregate"),
    "device_wait_ms": ("device-sync",),
    "queue_wait_ms": ("admission-wait", "batcher-queue-wait"),
    "encode_ms": ("encode",),
    "unattributed_ms": ("query", "execute"),
}
WAITS = ("admission-wait", "batcher-queue-wait", "device-sync")
WRITE_PATH = ("gateway-parse", "wal-append", "shard-ingest", "flush",
              "flush-encode", "flush-write")
QUERIES = "filodb_query_latency_seconds_count"


def family(stage, kind):
    """-> ``filodb_stage_<S>_<kind>``; kind: ``calls_total``,
    ``self_seconds_total`` or ``cpu_seconds_total``."""
    return f"filodb_stage_{stage.replace('-', '_')}_{kind}"


def stage_of(fam):
    """The stage a ``filodb_stage_*_self_seconds_total`` family belongs to
    (hyphens restored), or None for any other family."""
    if not (fam.startswith("filodb_stage_")
            and fam.endswith("_self_seconds_total")):
        return None
    return fam[len("filodb_stage_"):-len("_self_seconds_total")] \
        .replace("_", "-")


def seconds(ctx, stages, kind="self_seconds_total"):
    return sum(ctx.delta(family(s, kind)) for s in stages)


def self_ms(ctx, metric):
    n = ctx.delta(QUERIES)
    s = seconds(ctx, ROWS[metric])
    if n <= 0 or s <= 0:
        return None
    return s / n * 1e3


def cpu_share(ctx):
    work = [s for row in ROWS.values() for s in row if s not in WAITS]
    wall = seconds(ctx, work)
    if wall <= 0:
        return None
    return 100.0 * seconds(ctx, work, "cpu_seconds_total") / wall
