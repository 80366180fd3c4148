"""The node child: the one process of a run that touches JAX and holds the chip.

Builds ``filodb_tpu.standalone.server.FiloServer(config).start()`` in-process
(the class ``main()`` builds), refuses to go on unless the devices are the
cell's, backfills the history from the seed (``backfill.py``), prints ONE
startup line

    {"port": .., "gateway_port": .., "device": {..}, "start_s": ..,
     "datagen_s": .., "backfill": {..}}

and then obeys one-line commands on stdin, answering each with one JSON line:

    trace_start <dir>       jax.profiler.start_trace
    trace_stop [<dump>]     stop, reduce the .xplane.pb here (trace_reduce.py);
                            with <dump>, also keep the reduced events there
    mem                     peak bytes of the fullest device
    quit                    stop the server and exit
"""

import argparse
import glob
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def say(obj):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def plant_fault(name):
    """Tests only (``tests/test_run.py``): break the timed path underneath the
    harness and see ``correct`` come out false."""
    if name == "alter_answer":
        # an answer altered where it is produced: the JSON encoders of the
        # HTTP tier see one row a thousandth too high
        from filodb_tpu.http import prom_json

        def wrap(fn):
            def altered(grid, *a, **kw):
                if len(grid.keys):
                    grid.values = grid.values.copy()
                    grid.values[0] = grid.values[0] * 1.001
                return fn(grid, *a, **kw)
            return altered
        prom_json.matrix_bytes = wrap(prom_json.matrix_bytes)
        prom_json.matrix = wrap(prom_json.matrix)
    elif name == "drop_rows":
        # an acknowledged write lost: every shard drops the newest row of
        # each run it ingests, and still counts it as ingested
        from filodb_tpu.core import memstore
        real = memstore.TimeSeriesPartition.ingest_batch

        def lossy(self, ts, cols):
            if len(ts) > 1 or self.last_timestamp is None:
                return real(self, ts, cols)
            return 1
        memstore.TimeSeriesPartition.ingest_batch = lossy
    elif name == "fused_interpret":
        # no fault: lets a CPU node take the fused group-sum path (the
        # Pallas kernel in interpret mode), as the program's own tests do
        from filodb_tpu.query import tpu
        tpu.FUSED_GROUPSUM_INTERPRET = True
    else:
        raise SystemExit(f"node: unknown fault {name!r}")


def devices_or_exit(chips, allow_cpu):
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not allow_cpu:
        raise SystemExit(f"node: JAX came up on {device}, not on a TPU")
    if device["platform"] == "tpu" and device["count"] < chips:
        raise SystemExit(f"node: {device['count']} chips, the cell asks "
                         f"for {chips}")
    return device


def peak_bytes():
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True, help="configs/<name>.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--scale", default=None, help="JSON: rehearsal sizes")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    t0 = time.monotonic()
    with open(args.config) as f:
        cfg = json.load(f)

    from filodb_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()           # <checkout>/.jax_cache, or the env's
    import jax
    # every program of a cell goes to the persistent cache, also the ones
    # that compile in under a second: the second run of a cell compiles none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = devices_or_exit(args.chips, args.allow_cpu)
    for name in (args.fault or "").split(","):
        if name:
            plant_fault(name)

    from filodb_tpu.standalone.server import FiloServer
    node_cfg = {**cfg["node"], "port": 0, "gateway-port": 0,
                "data-dir": os.path.join(args.workdir, "data"),
                "stream-dir": os.path.join(args.workdir, "streams")}
    server = FiloServer(node_cfg).start()
    t1 = time.monotonic()
    datagen = importlib.import_module("datagen." + cfg["datagen"])
    world = datagen.make(cfg, args.seed,
                         json.loads(args.scale) if args.scale else None)
    t2 = time.monotonic()
    from backfill import backfill
    filled = backfill(server, world)
    del world
    say({"port": server.port, "gateway_port": server.gateway.port,
         "device": device, "start_s": t1 - t0, "datagen_s": t2 - t1,
         "backfill": filled})

    trace_dir = None
    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        try:
            if cmd[0] == "trace_start":
                trace_dir = cmd[1]
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                say({"ok": True, "t": time.time()})
            elif cmd[0] == "trace_stop":
                t_stop = time.time()    # collecting a long trace takes
                jax.profiler.stop_trace()   # seconds: not the window's
                import trace_reduce
                pbs = sorted(glob.glob(os.path.join(
                    trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
                events = trace_reduce.load_xplane(pbs[-1])
                if len(cmd) > 1:
                    trace_reduce.dump_events(events, cmd[1])
                out = trace_reduce.reduce(events)
                out["trace_bytes"] = os.path.getsize(pbs[-1])
                out["t"] = t_stop
                say({"ok": True, **out})
            elif cmd[0] == "mem":
                say({"ok": True, "memory_peak_bytes": peak_bytes()})
            elif cmd[0] == "quit":
                break
            else:
                say({"ok": False, "error": f"unknown command {cmd[0]}"})
        except Exception as e:      # noqa: BLE001 — the parent decides
            say({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]})
    server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
