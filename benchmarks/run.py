#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the machine it is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent (this process) NEVER imports JAX: the node child (``node.py``)
holds the chip. The parent makes the run's samples from ``--seed``, starts the
child (which makes the same samples and backfills them), warms up every
selection and shape the window will use, opens the window, drives the traffic
mix of the cell's data file (``traffic.py``) against the node's HTTP port and
gateway port, and afterwards compares a seeded sample of the answers the timed
requests themselves returned with the plain reference (``reference.py``).

The last line of standard output is the one JSON object the driver reads.
No TPU, or fewer chips than the cell asks for: no result, exit code 3.
Every metric is a file of its own (``end_to_end/<name>.py``,
``layers/<name>.py``) that the harness finds by the name in BENCHMARK.json;
see README.md for how a later PR adds a configuration, a cell or a metric.
"""

import time

T_START = time.monotonic()          # set-up is counted from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import select                       # noqa: E402
import shutil                       # noqa: E402
import subprocess                   # noqa: E402
import sys                          # noqa: E402
import tempfile                     # noqa: E402
import traceback                    # noqa: E402

import numpy as np                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import reference                    # noqa: E402
import traffic as traffic_mod       # noqa: E402
from client import KeepAliveClient, scrape_metrics  # noqa: E402

NO_CHIP = 3


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """``<kind>/<name>.py``, found by the metric's name (which may hold
    ``.`` and ``-``, so not by ``import``)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """What BENCHMARK.json and the files it names say about one cell."""

    def __init__(self, cell_name, candidate=None):
        self.bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        if candidate:               # a cell not admitted yet (rehearsals)
            for section, entries in load_json(os.path.join(
                    HERE, "candidates", candidate + ".json")).items():
                if isinstance(entries, list):
                    self.bench[section] = self.bench[section] + entries
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if cell_name not in cells:
            raise SystemExit(f"no cell {cell_name!r} in BENCHMARK.json "
                             f"(cells: {sorted(cells)})")
        self.cell = cells[cell_name]
        entry = next(c for c in self.bench["configs"]
                     if c["name"] == self.cell["config"])
        self.config_file = os.path.join(ROOT, entry["file"])
        self.config = load_json(self.config_file)
        self.workload = load_json(os.path.join(
            HERE, "workloads", self.cell["traffic"] + ".json"))

    def metrics(self, section):
        """The cell's metrics of one section. A metric lists its cells under
        ``workloads``; a per-layer metric without the key is read in every
        cell that reports the end-to-end metric it moves."""
        name = self.cell["name"]
        mine = [m for m in self.bench["end_to_end"]
                if name in m.get("workloads", [name])]
        if section == "end_to_end":
            return mine
        moved = {m["name"] for m in mine}
        return [m for m in self.bench["per_layer"]
                if (name in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]


class Node:
    """The node child and its one-line command protocol."""

    def __init__(self, spec, seed, workdir, scale, look_for_chip, fault):
        assert "jax" not in sys.modules, "the parent must never import JAX"
        cmd = [sys.executable, os.path.join(HERE, "node.py"),
               "--config", spec.config_file, "--seed", str(seed),
               "--chips", str(spec.cell["chips"]), "--workdir", workdir]
        if scale:
            cmd += ["--scale", json.dumps(scale)]
        if not look_for_chip:
            cmd += ["--allow-cpu"]
        if fault:
            cmd += ["--fault", fault]
        self.stderr_path = os.path.join(workdir, "node.stderr")
        self._log = open(self.stderr_path, "w")
        self.proc = subprocess.Popen(cmd, cwd=ROOT, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, stderr=self._log)
        self._buf = b""

    def read_line(self, timeout):
        deadline = time.monotonic() + timeout
        while b"\n" not in self._buf:
            left = deadline - time.monotonic()
            if left <= 0:
                raise RuntimeError(f"the node said nothing for {timeout} s")
            if select.select([self.proc.stdout], [], [], min(left, 1.0))[0]:
                chunk = self.proc.stdout.read1(65536)
                if not chunk:
                    raise RuntimeError("the node exited: "
                                       + self.stderr_tail(1500))
                self._buf += chunk
            elif self.proc.poll() is not None:
                raise RuntimeError("the node exited: "
                                   + self.stderr_tail(1500))
        line, self._buf = self._buf.split(b"\n", 1)
        return json.loads(line)

    def cmd(self, line, timeout=120):
        self.proc.stdin.write((line + "\n").encode())
        self.proc.stdin.flush()
        out = self.read_line(timeout)
        if not out.get("ok"):
            raise RuntimeError(f"node: {line.split()[0]}: {out.get('error')}")
        return out

    def stderr_tail(self, n=4000):
        if not self._log.closed:
            self._log.flush()
        with open(self.stderr_path, errors="replace") as f:
            return f.read()[-n:]

    def stop(self):
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write(b"quit\n")
                self.proc.stdin.flush()
                self.proc.wait(timeout=30)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        self._log.close()


class Ctx:
    """What a metric's reader may read. Readers return a number, or None
    where they find nothing to read (the metric is then left out)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def delta(self, family):
        return self.m1.get(family, 0.0) - self.m0.get(family, 0.0)


# -- correctness --------------------------------------------------------------

def compare_answers(spec, world, done, seed, control, controls_too=False):
    """A seeded sample of the window's answered requests against the plain
    reference. -> {"program" | control: (widest relative gap, answers
    compared, where it was read)}. With ``control`` the reference in that
    mode stands in the program's place; ``controls_too`` reads the program
    and every control over the same sample in one process."""
    ok = [d for d in done if d.status == 200]
    n = min(spec.workload["check"]["sample"], len(ok))
    modes = [control or "program"] + (
        [c for c in reference.CONTROLS if c != control] if controls_too
        else [])
    out = {m: [0.0 if n else float("inf"), n, None] for m in modes}
    rng = np.random.default_rng([seed, 1 << 21])
    picks = sorted(rng.choice(len(ok), size=n, replace=False).tolist()) \
        if n else []
    for i in picks:
        r = ok[i].req
        want, steps_s = reference.evaluate(world, r.query, r.start_s,
                                           r.end_s, r.step_s)
        keys = traffic_mod.key_labels(r.query)
        for mode in modes:
            body = ok[i].body
            if mode != "program":
                rows, _ = reference.evaluate(world, r.query, r.start_s,
                                             r.end_s, r.step_s, control=mode)
                body = reference.render_matrix(rows, steps_s, keys)
            try:
                got = reference.parse_matrix(body, keys)
                err, where = reference.max_rel_err(got, want, steps_s)
            except (ValueError, KeyError) as e:
                err, where = float("inf"), [f"answer not parsed: {e}"[:200]]
            if err > out[mode][0]:
                out[mode][0] = err
                out[mode][2] = {"query": traffic_mod.render(r.query),
                                "start": r.start_s, "step": r.step_s,
                                "where": where}
    return out


def readback(spec, world, node, traffic, seed):
    """Cell with writes: a seeded sample of series read back over the live
    scrapes that were sent and acknowledged. -> samples that differ from what
    was sent."""
    rb = spec.workload["check"].get("readback_series", 0)
    if not traffic.scrape or not rb or not traffic.scrape.scrapes_sent:
        return None
    rng = np.random.default_rng([seed, 1 << 22])
    k0 = world.n_hist
    k1 = k0 + traffic.scrape.scrapes_sent
    conn = KeepAliveClient(node["port"])
    bad = 0
    for i in rng.choice(world.n_series, size=rb, replace=False).tolist():
        l = world.labels[i]
        sel = ",".join(f'{k}="{v}"' for k, v in l.items() if k != "_metric_")
        # a bare selector at a 1 s step returns, at every second, the newest
        # sample at or before it: at the second a sample lands on it has to
        # be that sample's value, and the second before still the previous
        # sample's (so the timestamp is held to the second too)
        at = -(-world.ts[i, k0 - 1:k1] // 1000)         # ceil, in s
        vals = world.vals[i, k0 - 1:k1]
        status, body = conn.get(traffic_mod.request_bytes(traffic.path, {
            "query": f'{l["_metric_"]}{{{sel}}}', "start": int(at[1]) - 1,
            "end": int(at[-1]), "step": 1, "cache": "false"}))
        res = json.loads(body)["data"]["result"] if status == 200 else []
        got = {int(float(t)): float(v)
               for t, v in (res[0]["values"] if res else [])}
        for k in range(1, at.size):
            if got.get(int(at[k])) != vals[k] \
                    or got.get(int(at[k]) - 1) != vals[k - 1]:
                bad += 1
    conn.close()
    return bad


# -- the run ------------------------------------------------------------------

def run_cell(cell_name, seed, seconds, trace, *, look_for_chip=True,
             scale=None, control=None, fault=None, keep=None,
             controls_too=False, candidate=None):
    """-> (exit code, result dict or None)."""
    spec = Spec(cell_name, candidate)
    tmp = os.environ.get("TMPDIR") or tempfile.gettempdir()
    workdir = tempfile.mkdtemp(prefix="bench_", dir=tmp)
    node = Node(spec, seed, workdir, scale, look_for_chip, fault)
    try:
        datagen = importlib.import_module("datagen." + spec.config["datagen"])
        world = datagen.make(spec.config, seed, scale)
        traffic_mod.check(spec.workload, world)
        try:
            up = node.read_line(1100)
        except RuntimeError as e:
            print(f"no node: {e}", file=sys.stderr)
            return NO_CHIP, None
        device = up["device"]
        if look_for_chip and (device["platform"] != "tpu"
                              or device["count"] < spec.cell["chips"]):
            print(f"no chip for this cell: {device}", file=sys.stderr)
            return NO_CHIP, None
        t_up = time.monotonic() - T_START
        traffic = traffic_mod.Traffic(spec.workload, world, up, seed,
                                      spec.config["node"].get(
                                          "dataset", "timeseries"))
        n_warm = traffic.warmup()
        t_warm = time.monotonic() - T_START
        traffic.prepare()
        mconn = KeepAliveClient(up["port"])
        trace_dir = os.path.join(workdir, "trace")
        if trace:
            t_tr0 = node.cmd(f"trace_start {trace_dir}")["t"]
        m0 = scrape_metrics(mconn)
        setup_s = time.monotonic() - T_START
        t_open, t_close = traffic.run(seconds)
        m1 = scrape_metrics(mconn)
        reduced = None
        if trace:
            dump = f" {os.path.join(keep, 'trace.json.gz')}" if keep else ""
            reduced = node.cmd("trace_stop" + dump, timeout=240)
            reduced["window_s"] = reduced["t"] - t_tr0
        mem = node.cmd("mem")["memory_peak_bytes"]
        traffic.finish()
        unread = readback(spec, world, up, traffic, seed)
        mconn.close()
        node.stop()                 # the program's state is freed
        disk = sum(os.path.getsize(os.path.join(d, f))
                   for d, _, fs in os.walk(workdir) for f in fs)
        t_ref = time.monotonic()
        done = [d for c in traffic.done for d in c]
        ok = [d for d in done if d.status == 200]
        if keep:
            with open(os.path.join(keep, "requests.json"), "w") as f:
                json.dump([[d.t0 - t_open, d.t1 - t_open, d.status]
                           for d in done], f)
        read = compare_answers(spec, world, done, seed, control,
                               controls_too)
        err, compared, where = read[control or "program"]
        ref_s = time.monotonic() - t_ref

        limits = spec.workload["check"]["limits"]
        rose = min((m1.get(f, 0.0) - m0.get(f, 0.0)
                    for f in spec.workload["must_rise"]), default=1.0)
        checks = {
            "max_rel_err": {"value": err, "limit": limits["max_rel_err"],
                            "at": where},
            "answers_compared": {"value": compared, "at_least": 1},
            "device_counters_rose": {"value": rose, "at_least": 1},
        }
        if unread is not None:
            checks["readback_samples_differ"] = {"value": unread, "limit": 0}
        correct = all(
            (c["value"] <= c["limit"]) if "limit" in c
            else (c["value"] >= c["at_least"]) for c in checks.values())
        if not look_for_chip and device["platform"] != "tpu":
            checks["rehearsal_on"] = {"value": device["platform"]}

        ctx = Ctx(spec=spec, world=world, seconds=seconds, setup_s=setup_s,
                  done=done, ok=ok,
                  lat_ms=np.sort(np.array([(d.t1 - d.t0) * 1e3 for d in ok])),
                  answered_in_window=sum(1 for d in ok if d.t1 <= t_close),
                  m0=m0, m1=m1, trace=reduced, traffic=traffic,
                  device=device,
                  peaks=load_json(os.path.join(HERE, "peaks.json")))
        section = "per_layer" if trace else "end_to_end"
        kind = "layers" if trace else "end_to_end"
        metrics = {}
        for m in spec.metrics(section):
            v = load_module(kind, m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev = {**device, "memory_peak_bytes": mem}
        result = {"correct": bool(correct), "attempted": len(done),
                  "failed": len(done) - len(ok), "metrics": metrics,
                  "device": dev}
        if trace:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            result["trace_info"] = {k: reduced[k] for k in (
                "span_s", "device_planes", "n_ops", "trace_bytes", "seen")}
        result["phases_s"] = {
            "node_up": t_up, "node_start": up["start_s"],
            "datagen": up["datagen_s"],
            "backfill_ingest": up["backfill"]["ingest_s"],
            "backfill_flush": up["backfill"]["flush_s"],
            "warmup": t_warm - t_up, "warmup_requests": n_warm,
            "prepare": setup_s - t_warm, "reference": ref_s,
            "disk_bytes_written": disk}
        if controls_too:
            result["control_readings"] = {
                m: {"max_rel_err": v[0], "at": v[2]} for m, v in read.items()
                if m != (control or "program")}
        result["checks"] = checks
        if not correct:
            sys.stderr.write("---- node stderr (tail) ----\n"
                             + node.stderr_tail() + "\n")
        for name, c in checks.items():
            print(f"check {name}: {json.dumps(c)}", file=sys.stderr)
        return (0 if correct else 1), result
    finally:
        node.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bf16", "stale"), default=None,
                    help="put the reference at lower precision, or a stale "
                         "answer, in the program's place: has to end "
                         "correct=false (never set by the driver)")
    ap.add_argument("--controls-too", action="store_true",
                    help="also read every control over the same sample of "
                         "answers (reported, not judged)")
    ap.add_argument("--keep", default=None,
                    help="a directory that exists: keep every request's send "
                         "and answer time there and, with --trace 1, the "
                         "reduced trace events")
    args = ap.parse_args(argv)
    try:
        code, result = run_cell(args.workload, args.seed, args.seconds,
                                args.trace, control=args.control,
                                keep=args.keep,
                                controls_too=args.controls_too)
    except Exception:               # noqa: BLE001 — no result, non-zero
        traceback.print_exc()
        return 2
    if result is None:
        return code
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
