"""histogram-dev's latency histograms: one ``prom-histogram`` series (FiloDB's
native form: ``sum``, ``count`` and every bucket in one row) for apps x jobs x
instances, scraped every ``scrape_s``; half of the series on the tick and half
with +-``jitter_s`` of jitter, as ``promperf_counters``.

Each scrape adds Poisson(``requests_per_scrape``) observations, binned by the
bucket bounds ``les`` (``+Inf`` last): a bin's count is Poisson with the mean
times the bin's probability under the job's latency distribution, a log-normal
whose median rises geometrically from ``median_s[0]`` (job 0) to
``median_s[1]`` (the last job), spread ``sigma``. The distributions are fixed
by the configuration; the seed draws the observations and the jitter. A reset
in every ``reset_every``-th series, at a scrape drawn from the seed, zeroes
every bucket, ``sum`` and ``count`` at that scrape (a restarted target that has
served nothing yet), so every bucket that held a count falls at once.

``classic(world)`` is the same data as classic Prometheus series: one
``<metric>_bucket`` counter a bucket, with an ``le`` label (for the tests).
"""

import math

import numpy as np

from world import World


def bin_probabilities(les, median_s, sigma):
    """-> [B]: probability of each bucket's bin, (le[b-1], le[b]]."""
    cdf = [0.5 * (1.0 + math.erf((math.log(le) - math.log(median_s))
                                 / (sigma * math.sqrt(2.0))))
           for le in les[:-1]] + [1.0]
    return np.diff(cdf, prepend=0.0)


def make(cfg, seed, scale=None):
    d = {**cfg["data"], **(scale or {})}
    if d["live_samples"]:
        raise ValueError("histogram_latency makes history only: "
                         "live_samples has to be 0")
    apps, jobs, inst = d["apps"], d["jobs"], d["instances"]
    n = d["history_samples"]
    dt_ms, jit_ms = d["scrape_s"] * 1000, d["jitter_s"] * 1000
    t0_ms = d["t0_ms"]
    les = np.array([float(le) for le in d["les"]])
    rng = np.random.default_rng(seed)
    s = apps * jobs * inst
    idx = np.arange(s)
    jittered = idx % 2 == 1
    ticks = t0_ms + np.arange(n, dtype=np.int64) * dt_ms
    ts = np.broadcast_to(ticks, (s, n)).copy()
    ts[jittered] += rng.integers(-jit_ms, jit_ms + 1,
                                 (int(jittered.sum()), n))
    lo, hi = d["median_s"]
    medians = lo * (hi / lo) ** (np.arange(jobs) / max(jobs - 1, 1))
    p = np.stack([bin_probabilities(les, m, d["sigma"]) for m in medians])
    job = idx // inst % jobs
    per_bin = rng.poisson(d["requests_per_scrape"]
                          * p[job][:, None, :], (s, n, les.size))
    # a bin's observations at its midpoint; the overflow bin at 1.5 x the
    # highest finite bound
    mids = np.append((np.append(0.0, les[:-2]) + les[:-1]) / 2.0,
                     1.5 * les[-2])
    vals = np.cumsum(np.cumsum(per_bin, axis=2), axis=1).astype(np.float64)
    sums = np.cumsum(per_bin @ mids, axis=1)
    for r in range(5, s, d["reset_every"]):
        k = int(rng.integers(n // 4, 3 * n // 4))
        vals[r, k:] -= vals[r, k].copy()
        sums[r, k:] -= sums[r, k]
    labels = []
    for a in range(apps):
        for j in range(jobs):
            for i in range(inst):
                labels.append({
                    "_ws_": d["ws"], "_ns_": f"App-{a:02d}",
                    "_metric_": d["metric"], "job": f"job-{j:02d}",
                    "instance": f"i-{a:02d}-{j:02d}-{i:04d}"})
    return World(schema="prom-histogram", field="h", labels=labels, ts=ts,
                 vals=vals, n_hist=n, t0_ms=t0_ms, dt_ms=dt_ms,
                 slack_ms=jit_ms, les=les, sums=sums)


def le_text(le):
    """A bound as Prometheus writes it in ``le``: ``0.005``, ``1``, ``+Inf``."""
    return "+Inf" if le == math.inf else f"{le:g}"


def classic(world):
    """-> the World of the same data as ``<metric>_bucket`` counters, one a
    series and bucket (series-major, bucket-minor)."""
    s, n, b = world.vals.shape
    labels = [{**l, "_metric_": l["_metric_"] + "_bucket", "le": le_text(le)}
              for l in world.labels for le in world.les]
    return World(schema="prom-counter", field="counter", labels=labels,
                 ts=np.repeat(world.ts, b, axis=0),
                 vals=world.vals.transpose(0, 2, 1).reshape(s * b, n),
                 n_hist=world.n_hist, t0_ms=world.t0_ms, dt_ms=world.dt_ms,
                 slack_ms=world.slack_ms)
