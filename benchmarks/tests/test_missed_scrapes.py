"""``promperf-missed-scrapes``: what its datagen promises, from two seeds,
and its cell end to end at a toy size on the CPU.

A CPU node takes the fused group-sum path only with the ``fused_interpret``
switch (no fault: the Pallas kernel in interpret mode), so the runs here set
it: since PR 33 the fused gate serves a query over the holes by its second
program, the grouped non-dense evaluator (``holes_fused_share`` 100,
``fused_share`` 100, ``gap_refusal_share`` 0, no batch formed), and over
``promperf``'s dense fleet by the kernel (``holes_fused_share`` 0).

    python -m pytest benchmarks/tests/test_missed_scrapes.py -q
"""

import importlib

import numpy as np
import pytest

import run

CELL = "promperf-missed-scrapes.history-sumby"
TOY = {"apps": 3, "jobs": 4, "instances": 16}
SEEDS = [11, 3000000019]


def _world(seed, scale=None):
    spec = run.Spec(CELL)
    datagen = importlib.import_module("datagen." + spec.config["datagen"])
    return spec.config["data"], datagen.make(spec.config, seed, scale)


@pytest.mark.parametrize("seed", SEEDS)
def test_datagen_keeps_its_promises(seed):
    d, w = _world(seed, TOY)
    n, dt = d["history_samples"], w.dt_ms
    n_missed = d["missed_singles"] + d["missed_run"]
    assert w.ts.shape == w.vals.shape == (w.n_series, n) and w.n_hist == n
    assert (np.diff(w.ts, axis=1) > 0).all()
    col_tick = w.t0_ms + np.arange(n) * dt
    assert np.abs(w.ts - col_tick).max() <= w.slack_ms
    assert w.slack_ms == d["jitter_s"] * 1000 + n_missed * dt
    tick = np.round((w.ts - w.t0_ms) / dt).astype(int)
    flaky = np.arange(w.n_series) % d["flaky_every"] == d["flaky_every"] - 1
    assert 0 < flaky.sum() < w.n_series
    for r in range(w.n_series):
        ticks = n + n_missed if flaky[r] else n
        missed = sorted(set(range(ticks)) - set(tick[r].tolist()))
        if not flaky[r]:
            assert not missed
            continue
        assert len(missed) == n_missed
        assert missed[0] >= 2 and missed[-1] < n + n_missed - 2
        # holes as runs of consecutive ticks: four of one and one of four
        runs = np.diff(np.flatnonzero(np.diff([-9] + missed + [10**9]) > 1))
        assert sorted(runs.tolist()) == [1] * d["missed_singles"] \
            + [d["missed_run"]]
    _, again = _world(seed, TOY)
    assert (again.ts == w.ts).all() and (again.vals == w.vals).all()
    _, other = _world(seed + 1, TOY)
    assert (other.ts != w.ts).any()


def test_every_window_lies_in_ticks_every_series_covers():
    import traffic
    spec = run.Spec(CELL)
    _, w = _world(SEEDS[0], TOY)
    tmpl = spec.workload["queries"][0]
    lo, hi = traffic.history_bounds(w, tmpl)
    assert lo < hi
    assert (w.ts[:, 0] <= (lo - tmpl["query"]["window_s"]) * 1000).all()
    assert (w.ts[:, -1] >= (hi + tmpl["range_s"]) * 1000).all()


def _toy_run(cell, trace=0, scale=TOY, **kw):
    return run.run_cell(cell, SEEDS[1], 3, trace, look_for_chip=False,
                        scale=scale, fault="fused_interpret", **kw)


def test_cell_end_to_end():
    code, result = _toy_run(CELL, trace=1)
    assert result["correct"] and code == 0, result["checks"]
    assert result["failed"] == 0
    m = result["metrics"]
    assert m["holes_fused_share"] == {"value": 100.0, "unit": "%"}
    assert m["fused_share"]["value"] == 100.0
    assert m["gap_refusal_share"]["value"] == 0.0
    assert m["tile_hit_share"]["value"] == 100.0
    assert m["window_compiles"]["value"] == 0.0
    assert "batch_occupancy" not in m       # the sum by left the batcher
    # the [T, G] sums and counts, far under one app's [31, 64] rate grid
    assert m["d2h_kb_per_query"]["unit"] == "KB"
    assert 0.0 < m["d2h_kb_per_query"]["value"] < 31 * 64 * 4 / 1e3


@pytest.mark.parametrize("control", ["bf16", "stale"])
def test_control_is_not_correct(control):
    code, result = _toy_run(CELL, control=control)
    c = result["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
    assert not result["correct"] and code != 0


def test_dense_fleet_refuses_nothing_for_holes():
    import rehearse
    code, result = _toy_run("promperf.history-sumby", trace=1,
                            scale=rehearse.TOY["promperf_counters"])
    assert result["correct"] and code == 0, result["checks"]
    m = result["metrics"]
    assert m["gap_refusal_share"]["value"] == 0.0
    assert m["fused_share"]["value"] == 100.0
    # two [T, G] float32 grids a query, T padded: far under the [T, S] grid
    assert 0.0 < m["d2h_kb_per_query"]["value"] < 31 * 64 * 4 / 1e3


def test_readers_find_nothing_on_a_program_without_the_counters():
    ctx = run.Ctx(ok=[object()], m0={}, m1={"filodb_fused_aggs_total": 3.0})
    for name in ("gap_refusal_share", "d2h_kb_per_query"):
        assert run.load_module("layers", name).read(ctx) is None
