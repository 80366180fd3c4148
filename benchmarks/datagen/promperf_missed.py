"""promperf's counters over a fleet in which some targets miss scrapes: a
scrape that fails or times out (``up == 0``) stores no sample, so the series
has a hole of one or more scrape intervals on the cadence grid.

The dense world of ``promperf_counters`` over ``history_samples`` + 8 ticks;
every ``flaky_every``-th series (index % ``flaky_every`` == ``flaky_every``
- 1) then loses ``missed_singles`` single ticks and one run of ``missed_run``
consecutive ticks, drawn from the seed, no two of them adjacent and none in
the row's first or last two ticks. Every row keeps ``history_samples``
columns: a clean row ticks 0..``history_samples`` - 1, a flaky row all the
ticks it did not miss. Column ``k`` of a flaky row therefore lies up to 8
ticks after tick ``k``, which ``slack_ms`` states. The deployment does not
write: ``live_samples`` has to be 0."""

import numpy as np

from datagen import promperf_counters
from world import World


def missed_ticks(n_rows, ticks, singles, run, rng):
    """-> bool [n_rows, ticks]: the ticks each row misses."""
    lens = np.ones((n_rows, singles + (run > 0)), dtype=np.int64)
    if run:
        lens[np.arange(n_rows), rng.integers(0, lens.shape[1], n_rows)] = run
    events = lens.shape[1]
    # the ticks between the first and the last two that are neither missed
    # nor the one kept tick between two events, dealt out around the events
    slack = ticks - 4 - int(lens[0].sum()) - (events - 1)
    if slack < 0:
        raise ValueError(f"{ticks} ticks do not hold {events} holes")
    free = np.sort(rng.integers(0, slack + 1, (n_rows, events)), axis=1)
    starts = 2 + free + np.cumsum(lens, axis=1) - lens + np.arange(events)
    missed = np.zeros((n_rows, ticks), dtype=bool)
    rows = np.arange(n_rows)
    for e in range(events):
        for o in range(max(run, 1)):
            on = o < lens[:, e]
            missed[rows[on], starts[on, e] + o] = True
    return missed


def make(cfg, seed, scale=None):
    d = {**cfg["data"], **(scale or {})}
    if d["live_samples"]:
        raise ValueError("promperf_missed makes history only: live_samples "
                         "has to be 0")
    n_hist = d["history_samples"]
    n_missed = d["missed_singles"] + d["missed_run"]
    dense = promperf_counters.make(
        {"data": {**d, "history_samples": n_hist + n_missed}}, seed)
    flaky = np.arange(dense.n_series) % d["flaky_every"] \
        == d["flaky_every"] - 1
    kept = ~missed_ticks(int(flaky.sum()), n_hist + n_missed,
                         d["missed_singles"], d["missed_run"],
                         np.random.default_rng([seed, 1 << 19]))
    ts = dense.ts[:, :n_hist].copy()
    vals = dense.vals[:, :n_hist].copy()
    ts[flaky] = dense.ts[flaky][kept].reshape(-1, n_hist)
    vals[flaky] = dense.vals[flaky][kept].reshape(-1, n_hist)
    return World(schema=dense.schema, field=dense.field, labels=dense.labels,
                 ts=ts, vals=vals, n_hist=n_hist, t0_ms=dense.t0_ms,
                 dt_ms=dense.dt_ms,
                 slack_ms=dense.slack_ms + n_missed * dense.dt_ms)
