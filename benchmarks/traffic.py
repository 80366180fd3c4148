"""The one traffic generator: every mix is a data file under ``workloads/``.

A mix is N closed-loop query clients (each sends its next ``query_range`` when
the last is answered) over a weighted set of query templates, and optionally
one scraper that writes the configuration's next samples through the gateway's
TCP influx port in scrape order, at most ``in_flight_scrapes`` beyond what the
node has acknowledged (``filodb_rows_ingested`` is the acknowledgement: the
TCP edge has none of its own).

Everything is drawn from ``--seed``: which template each request uses (a fixed
multiset in seeded order, so every seed does the same work in another order),
the label values it selects (uniform or Zipf over the values the world has)
and where its range lies.

    {"clients": 4,
     "queries": [{"name": .., "weight": 1, "range_s": 1800, "step_s": 60,
                  "end": "history" | "now",
                  "query": {"metric", "select": {label: "$var" | value},
                            "fn", "window_s", "agg", "by", "key_label",
                            "quantile"},
                  "draw": {"var": {"label": .., "dist": "zipf"|"uniform"|
                                   "fixed", "s": 1.0, "k": 1, "values": []}}}],
     "warmup": {"each": ["var"], "max_rounds": 6, "round_s": 3},
     "scrape": {"in_flight_scrapes": 1} | null,
     "check": {"sample": 64, "limits": {"max_rel_err": 1e-5}},
     "must_rise": ["filodb_fused_aggs_total"]}

A query with ``"quantile": q`` (0 < q < 1, only over ``"agg": "sum"`` of a
rate) is ``histogram_quantile(q, <the aggregation>)``: over a world of native
histograms ``by`` names the result's labels, over classic ``le`` series it
names ``le`` too (``check`` refuses a mix that does not fit its world).
"""

import re
import socket
import threading
import time

import numpy as np

from client import KeepAliveClient, request_bytes, scrape_metrics
from reference import RATE_FNS

PREGEN = 4096                  # requests drawn per client before the window
MISSES = "filodb_exec_cache_misses_total"


# -- queries ------------------------------------------------------------------

def render(q):
    """The structured query -> the PromQL string the node is sent."""
    sel = [f'{k}="{v}"' if not isinstance(v, list)
           else f'{k}=~"{"|".join(v)}"' for k, v in q.get("select", {}).items()]
    inner = (f'{q["fn"]}({q["metric"]}{{{",".join(sel)}}}'
             f'[{q["window_s"]}s])')
    if not q.get("agg"):
        return inner
    by = f' by ({",".join(q["by"])})' if q.get("by") else ""
    out = f'{q["agg"]}({inner}){by}'
    if "quantile" in q:
        out = f'histogram_quantile({q["quantile"]!r}, {out})'
    return out


def key_labels(q):
    if not q.get("agg"):
        return [q.get("key_label", "instance")]
    by = list(q.get("by", []))
    return [b for b in by if b != "le"] if "quantile" in q else by


def check(workload, world):
    """Refuse, as the mix is loaded, what the window could not send or the
    reference could not answer."""
    for tmpl in workload["queries"]:
        q = tmpl["query"]
        if "quantile" in q:
            if not (0 < q["quantile"] < 1 and q.get("agg") == "sum"
                    and q["fn"] in RATE_FNS):
                raise ValueError(
                    f"{tmpl['name']}: a quantile needs 0 < q < 1 over "
                    "\"agg\": \"sum\" of rate or increase")
            if world.les is None and "le" not in q.get("by", []):
                raise ValueError(f"{tmpl['name']}: a quantile over classic "
                                 "bucket series needs \"le\" in \"by\"")
        elif world.les is not None:
            raise ValueError(f"{tmpl['name']}: a world of histograms is "
                             "read through a quantile only")
    if world.les is not None and workload.get("scrape"):
        raise ValueError("a world of histograms is history only: live "
                         "histogram writes are out of the harness's scope")


def bind(q, values):
    """Substitute drawn values for the ``$var`` selectors."""
    sel = {}
    for label, v in q.get("select", {}).items():
        if isinstance(v, str) and v.startswith("$"):
            v = values[v[1:]]
            v = v[0] if len(v) == 1 else list(v)
        sel[label] = v
    return {**q, "select": sel}


def draw_values(world, spec, rng):
    vals = spec.get("values") or world.label_values(spec["label"])
    k = spec.get("k", 1)
    dist = spec.get("dist", "uniform")
    if dist == "fixed":
        return vals[:k]
    p = None
    if dist == "zipf":
        p = 1.0 / np.arange(1, len(vals) + 1) ** spec.get("s", 1.0)
        p /= p.sum()
    pick = rng.choice(len(vals), size=k, replace=False, p=p)
    return [vals[i] for i in pick]


def history_bounds(world, tmpl):
    """Whole minutes at which a range may start so that every window lies in
    the backfilled history."""
    first = (world.t0_ms + world.slack_ms) // 1000 \
        + tmpl["query"]["window_s"]
    last = (world.t0_ms + (world.n_hist - 1) * world.dt_ms
            - world.slack_ms) // 1000
    lo = -(-first // 60) * 60
    hi = (last - tmpl["range_s"]) // 60 * 60
    if hi < lo:
        raise ValueError(f"{tmpl['name']}: a range of {tmpl['range_s']} s "
                         "does not fit the history")
    return lo, hi


class Request:
    __slots__ = ("tmpl", "query", "start_s", "end_s", "step_s", "raw")

    def __init__(self, tmpl, query, start_s, end_s, path):
        self.tmpl, self.query = tmpl, query
        self.start_s, self.end_s, self.step_s = start_s, end_s, tmpl["step_s"]
        self.raw = request_bytes(path, {
            "query": render(query), "start": start_s, "end": end_s,
            "step": self.step_s, "cache": "false"})


def make_request(world, tmpl, rng, path, now_end_s=None):
    values = {var: draw_values(world, spec, rng)
              for var, spec in tmpl.get("draw", {}).items()}
    q = bind(tmpl["query"], values)
    if tmpl.get("end", "history") == "now":
        end = now_end_s
        start = end - tmpl["range_s"]
    else:
        lo, hi = history_bounds(world, tmpl)
        start = lo + 60 * int(rng.integers(0, (hi - lo) // 60 + 1))
        end = start + tmpl["range_s"]
    return Request(tmpl, q, start, end, path)


def template_order(templates, n, rng):
    """A fixed multiset of templates by weight, in seeded order."""
    w = np.array([t.get("weight", 1) for t in templates], dtype=np.float64)
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    order = np.repeat(np.arange(len(templates)), counts)
    rng.shuffle(order)
    return order


# -- the run ------------------------------------------------------------------

class Done:
    """One answered (or failed) request of the window."""
    __slots__ = ("req", "t0", "t1", "status", "body")

    def __init__(self, req, t0, t1, status, body):
        self.req, self.t0, self.t1 = req, t0, t1
        self.status, self.body = status, body


class Traffic:
    def __init__(self, workload, world, node, seed, dataset):
        self.w, self.world, self.node, self.seed = workload, world, node, seed
        self.path = f"/promql/{dataset}/api/v1/query_range"
        self.done = []              # per client: [Done]
        self.acked = self.scrape = None
        if workload.get("scrape"):  # the mix writes: one scraper, one monitor
            self.acked = AckMonitor(node, world)
            self.scrape = Scraper(node, world, self.acked, workload["scrape"])
            self.acked.start()

    # warm-up: every shape and every selection the window can use ---------
    def warmup(self):
        """-> requests sent. Each value of the ``each`` variables twice per
        template (the selections whose tiles the window reuses), then rounds
        of the mix itself from another seed stream (the shapes)."""
        rng = np.random.default_rng([self.seed, 1 << 20])
        conn = KeepAliveClient(self.node["port"], timeout=900)
        wu = self.w.get("warmup", {})
        # a template that ends at now is warmed up at the end of the history
        hist_end = self.world.tick_s(self.world.n_hist - 1) \
            + self.world.slack_ms // 1000
        reqs = []
        for tmpl in self.w["queries"]:
            for var, spec in tmpl.get("draw", {}).items():
                if var not in wu.get("each", []):
                    continue
                for v in spec.get("values") or self.world.label_values(
                        spec["label"]):
                    one = {**tmpl, "draw": {**tmpl["draw"], var: {
                        "values": [v], "dist": "fixed"}}}
                    reqs += [make_request(self.world, one, rng, self.path,
                                          now_end_s=hist_end)] * 2
        for req in reqs:
            status, body = conn.get(req.raw)
            if status != 200:
                raise RuntimeError(f"warm-up got {status}: {body[:300]!r}")
        n = len(reqs)
        # then the mix itself, from another seed stream, with the window's
        # own concurrency (the batcher's batch shapes exist only under it),
        # in rounds until a round compiles nothing
        rounds = wu.get("max_rounds", 0)
        if rounds:
            self.prepare(stream=1 << 20)
        for _ in range(rounds):
            before = scrape_metrics(conn).get(MISSES, 0.0)
            self.run(wu["round_s"])
            n += sum(len(c) for c in self.done)
            bad = [d for c in self.done for d in c if d.status != 200]
            if bad:
                raise RuntimeError(f"warm-up got {bad[0].status}: "
                                   f"{bad[0].body[:300]!r}")
            if scrape_metrics(conn).get(MISSES, 0.0) == before:
                break
        conn.close()
        if self.scrape:             # nothing in flight when the window opens
            self.acked.wait_all(self.scrape.rows_sent, timeout=60)
        return n

    def prepare(self, stream=0):
        """Draw every client's requests: set-up, so that the window only
        sends."""
        self.plans = []
        for c in range(self.w["clients"]):
            rng = np.random.default_rng([self.seed, stream + c])
            order = template_order(self.w["queries"], PREGEN, rng)
            plan = []
            for t in order:
                tmpl = self.w["queries"][t]
                if tmpl.get("end", "history") == "now":
                    plan.append((tmpl, rng))
                else:
                    plan.append(make_request(self.world, tmpl, rng,
                                             self.path))
            self.plans.append(plan)

    def _client(self, c, t_close):
        conn = KeepAliveClient(self.node["port"])
        out, plan, i = self.done[c], self.plans[c], 0
        while time.perf_counter() < t_close:
            item = plan[i % len(plan)]
            i += 1
            if not isinstance(item, Request):
                tmpl, rng = item
                item = make_request(self.world, tmpl, rng, self.path,
                                    now_end_s=self.acked.visible_end_s())
            t0 = time.perf_counter()
            try:
                status, body = conn.get(item.raw)
            except OSError as e:
                status, body = 0, str(e).encode()
            out.append(Done(item, t0, time.perf_counter(), status, body))
        conn.close()

    def run(self, seconds):
        """The measured window (and each warm-up round): the clients and,
        where the mix writes, the scraper. -> (t_open, t_close) on
        perf_counter."""
        self.done = [[] for _ in range(self.w["clients"])]
        t_open = time.perf_counter()
        t_close = t_open + seconds
        threads = [threading.Thread(target=self._client,
                                    args=(c, t_close), daemon=True)
                   for c in range(self.w["clients"])]
        if self.scrape:
            self.acked.mark_open()
            threads.append(threading.Thread(
                target=self.scrape.run, args=(t_close,), daemon=True))
        for t in threads:
            t.start()
        time.sleep(max(0.0, t_close - time.perf_counter()))
        if self.scrape:
            self.acked.mark_close()
        for t in threads:
            t.join(timeout=120)
        if any(t.is_alive() for t in threads):
            raise RuntimeError("a client had no answer 120 s past the close")
        if self.scrape and self.scrape.ran_out:
            raise RuntimeError("the scraper ran out of live samples: raise "
                               "the configuration's live_samples")
        return t_open, t_close

    def finish(self):
        """After the window: wait for what was sent to be acknowledged."""
        if self.scrape:
            self.acked.wait_all(self.scrape.rows_sent, timeout=60)
            self.acked.stop()


# -- writes -------------------------------------------------------------------

class AckMonitor(threading.Thread):
    """Polls ``/metrics`` for ``filodb_rows_ingested`` per shard: the node's
    acknowledgement of the rows the scraper sent."""
    FAMILY = "filodb_rows_ingested"
    POLL_S = 0.05

    def __init__(self, node, world):
        super().__init__(daemon=True)
        self.node, self.world = node, world
        self.conn = KeepAliveClient(node["port"])
        self.lock = threading.Lock()
        self.stop_ev = threading.Event()
        self.shard_series = {int(k): v for k, v in
                             node["backfill"]["shard_series"].items()}
        self.base = self._read()
        self.rows = self.base
        self.t_last_ack = time.perf_counter()
        self.at_open = self.at_close = None

    def _read(self):
        m = scrape_metrics(self.conn, keep_labels=(self.FAMILY,))
        out = {}
        for name, v in m.items():
            got = re.match(self.FAMILY + r'\{.*shard="(\d+)"', name)
            if got:
                out[int(got.group(1))] = out.get(int(got.group(1)), 0) + v
        return out

    def run(self):
        while not self.stop_ev.wait(self.POLL_S):
            rows = self._read()
            with self.lock:
                if rows != self.rows:
                    self.t_last_ack = time.perf_counter()
                self.rows = rows

    def stop(self):
        self.stop_ev.set()
        self.join(timeout=10)
        self.conn.close()

    def total(self):
        with self.lock:
            return sum(self.rows.values()) - sum(self.base.values())

    def mark_open(self):
        self.at_open = self.total()

    def mark_close(self):
        self.at_close = self.total()

    def scrapes_acked(self):
        """Live scrapes of which every shard has ingested every row."""
        with self.lock:
            return int(min((self.rows.get(s, 0) - self.base.get(s, 0)) // n
                           for s, n in self.shard_series.items() if n))

    def visible_end_s(self):
        """The newest whole second at which every sample is acknowledged:
        the last fully acknowledged scrape's tick plus the jitter."""
        k = self.world.n_hist + self.scrapes_acked() - 1
        return self.world.tick_s(k) + self.world.slack_ms // 1000

    def wait_all(self, rows_sent, timeout):
        deadline = time.perf_counter() + timeout
        while self.total() < rows_sent:
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    f"{rows_sent - self.total()} of {rows_sent} rows sent "
                    f"were not acknowledged {timeout} s past the close")
            time.sleep(self.POLL_S)


class Scraper:
    """One connection to the gateway, every series' next sample per scrape,
    in scrape order."""

    def __init__(self, node, world, acked, spec):
        self.node, self.world, self.acked = node, world, acked
        self.in_flight = spec.get("in_flight_scrapes", 1)
        w = world
        prefixes = w.influx_prefixes()
        self.payloads = []
        for k in range(w.n_hist, w.ts.shape[1]):
            vals = w.vals[:, k].astype(np.int64).tolist() \
                if w.schema == "prom-counter" else w.vals[:, k].tolist()
            lines = [f"{p}{v!r} {t}000000" for p, v, t in
                     zip(prefixes, vals, w.ts[:, k].tolist())]
            self.payloads.append(("\n".join(lines) + "\n").encode())
        self.rows_sent = 0
        self.scrapes_sent = 0
        self.t_last_sent = None
        self.ran_out = False

    def run(self, t_close):
        """Send on from where the last call stopped, until ``t_close``."""
        s = self.world.n_series
        with socket.create_connection(
                ("127.0.0.1", self.node["gateway_port"]), timeout=120) as sk:
            while True:
                if self.scrapes_sent == len(self.payloads):
                    self.ran_out = True     # the configuration's
                    return                  # live_samples are too few
                payload = self.payloads[self.scrapes_sent]
                while (self.acked.total() < self.rows_sent
                       - self.in_flight * s):
                    if time.perf_counter() >= t_close:
                        return
                    time.sleep(0.005)
                if time.perf_counter() >= t_close:
                    return
                sk.sendall(payload)
                self.rows_sent += s
                self.scrapes_sent += 1
                self.t_last_sent = time.perf_counter()
