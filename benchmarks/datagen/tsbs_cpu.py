"""TSBS devops ``cpu-only``: ten ``cpu_usage_*`` gauges per host, each a
random walk (a standard-normal step per scrape) clamped to 0..100, with the
ten host tags TSBS gives every host."""

import numpy as np

from world import World

REGIONS = ["us-east-1", "us-west-1", "us-west-2", "eu-west-1", "eu-central-1",
           "ap-southeast-1", "ap-southeast-2", "ap-northeast-1", "sa-east-1"]
OSES = ["Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10"]
ARCHES = ["x64", "x86"]
TEAMS = ["SF", "NYC", "LON", "CHI"]
ENVS = ["production", "staging", "test"]


def make(cfg, seed, scale=None):
    d = {**cfg["data"], **(scale or {})}
    hosts, metrics = d["hosts"], d["metrics"]
    n_hist, n = d["history_samples"], d["history_samples"] + d["live_samples"]
    dt_ms, t0_ms = d["scrape_s"] * 1000, d["t0_ms"]
    rng = np.random.default_rng(seed)
    s = hosts * len(metrics)
    ticks = t0_ms + np.arange(n, dtype=np.int64) * dt_ms
    ts = np.broadcast_to(ticks, (s, n)).copy()
    vals = np.empty((s, n))
    cur = rng.uniform(0.0, 100.0, s)
    steps = rng.standard_normal((n, s))
    for k in range(n):
        cur = np.clip(cur + steps[k], 0.0, 100.0)
        vals[:, k] = cur
    tag = rng.integers(0, 1 << 30, (hosts, 8))
    labels = []
    for h in range(hosts):
        region = REGIONS[tag[h, 0] % len(REGIONS)]
        host_tags = {
            "_ws_": d["ws"], "_ns_": d["ns"],
            "hostname": f"host_{h}", "region": region,
            "datacenter": f"{region}{'abc'[tag[h, 1] % 3]}",
            "rack": str(tag[h, 2] % 100), "os": OSES[tag[h, 3] % 3],
            "arch": ARCHES[tag[h, 4] % 2], "team": TEAMS[tag[h, 5] % 4],
            "service": str(tag[h, 6] % 20),
            "service_version": str(tag[h, 7] % 2),
            "service_environment": ENVS[tag[h, 7] // 2 % 3]}
        for m in metrics:
            labels.append({**host_tags, "_metric_": m})
    return World(schema="gauge", field="gauge", labels=labels, ts=ts,
                 vals=vals, n_hist=n_hist, t0_ms=t0_ms, dt_ms=dt_ms,
                 slack_ms=0)
