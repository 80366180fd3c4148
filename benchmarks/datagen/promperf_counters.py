"""promperf's counters: ``http_requests_total`` for apps x jobs x instances,
scraped every ``scrape_s``; half of the series on the tick and half with
+-``jitter_s`` of jitter, a counter reset in every ``reset_every``-th series
(``chip_smoke.py``'s World, which reaches the fused kernel on the chip)."""

import numpy as np

from world import World


def make(cfg, seed, scale=None):
    d = {**cfg["data"], **(scale or {})}
    apps, jobs, inst = d["apps"], d["jobs"], d["instances"]
    n_hist, n = d["history_samples"], d["history_samples"] + d["live_samples"]
    dt_ms, jit_ms = d["scrape_s"] * 1000, d["jitter_s"] * 1000
    t0_ms = d["t0_ms"]
    rng = np.random.default_rng(seed)
    s = apps * jobs * inst
    idx = np.arange(s)
    jittered = idx % 2 == 1
    ticks = t0_ms + np.arange(n, dtype=np.int64) * dt_ms
    ts = np.broadcast_to(ticks, (s, n)).copy()
    ts[jittered] += rng.integers(-jit_ms, jit_ms + 1,
                                 (int(jittered.sum()), n))
    vals = np.cumsum(rng.integers(0, 50, (s, n)), axis=1)
    for r in range(5, s, d["reset_every"]):
        k = int(rng.integers(n_hist // 4, 3 * n_hist // 4))
        vals[r, k:] -= vals[r, k - 1]
    labels = []
    for a in range(apps):
        for j in range(jobs):
            for i in range(inst):
                labels.append({
                    "_ws_": d["ws"], "_ns_": f"App-{a:02d}",
                    "_metric_": d["metric"], "job": f"job-{j:02d}",
                    "instance": f"i-{a:02d}-{j:02d}-{i:04d}"})
    return World(schema="prom-counter", field="counter", labels=labels,
                 ts=ts, vals=vals.astype(np.float64), n_hist=n_hist,
                 t0_ms=t0_ms, dt_ms=dt_ms, slack_ms=jit_ms)
