"""The samples of one run, as the parent and the node child both make them
from ``--seed``: the same seed gives the same arrays in both processes.

``datagen/<name>.py`` (named by the configuration's ``datagen`` key) builds
one; ``backfill.py`` puts its first ``n_hist`` columns into the node,
``traffic.py`` sends the rest as live scrapes, ``reference.py`` answers
queries from it.

A world of histograms (``les`` given: the bucket bounds, ascending, ``+Inf``
last) holds in ``vals`` the cumulative bucket counts, float64 ``[S, N, B]``,
and in ``sums`` the histogram's ``sum`` column, float64 ``[S, N]``; its
``count`` column is the ``+Inf`` bucket. Without ``les`` ``vals`` is float64
``[S, N]``, one value a sample, and ``sums`` is None.
"""

import numpy as np


class World:
    def __init__(self, *, schema, field, labels, ts, vals, n_hist, t0_ms,
                 dt_ms, slack_ms, les=None, sums=None):
        self.schema = schema        # the program's schema name
        self.field = field          # the influx field that maps to it
        self.labels = labels        # per series: the full label map
        self.ts = ts                # int64 ms [S, N], sorted per row
        self.vals = vals            # float64 [S, N], or [S, N, B] with les
        self.les = les              # bucket bounds, +Inf last, or None
        self.sums = sums            # float64 [S, N] with les, or None
        self.n_hist = n_hist        # columns [0, n_hist) are history
        self.t0_ms, self.dt_ms = t0_ms, dt_ms
        self.slack_ms = slack_ms    # |ts - tick| never exceeds it
        self._cols = {}

    @property
    def n_series(self):
        return len(self.labels)

    def label_column(self, label):
        col = self._cols.get(label)
        if col is None:
            col = self._cols[label] = np.array(
                [l.get(label, "") for l in self.labels])
        return col

    def label_values(self, label):
        vals = self._cols.get(("values", label))
        if vals is None:
            vals = self._cols[("values", label)] = sorted(
                set(self.label_column(label).tolist()) - {""})
        return vals

    def tick_s(self, k):
        """Nominal time of scrape ``k`` in whole seconds."""
        return (self.t0_ms + k * self.dt_ms) // 1000

    def influx_prefixes(self):
        """Per series, the part of its influx line before the value."""
        out = []
        for l in self.labels:
            tags = ",".join(f"{k}={v}" for k, v in l.items()
                            if k != "_metric_")
            out.append(f"{l['_metric_']},{tags} {self.field}=")
        return out
