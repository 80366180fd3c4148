"""The stage counters against the harness, at a toy size on the CPU: after a
rehearsal of each cell (``--trace 1``, so the per-layer readers run), every
``filodb_stage_*_self_seconds_total`` family that rose in the window is in
exactly one row of ``stages.py`` (or among its write-path stages), every new
reader returns a number, and the rows account for ``server_query_ms``.

    python -m pytest benchmarks/tests/test_stages.py -q
"""

import pytest

import rehearse
import run
import stages

CELLS = {"promperf.history-sumby": "fused_interpret",
         "tsbs-devops.host-dashboards": None}
NEW = sorted(stages.ROWS) + ["host_cpu_share"]


def test_every_query_path_stage_is_in_exactly_one_row():
    seen = [s for row in stages.ROWS.values() for s in row]
    assert len(seen) == len(set(seen))
    assert not set(seen) & set(stages.WRITE_PATH)
    assert set(stages.WAITS) <= set(seen)
    assert stages.stage_of(stages.family("select-series",
                                         "self_seconds_total")) \
        == "select-series"
    assert stages.stage_of("filodb_query_latency_seconds_sum") is None


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rows_account_for_the_nodes_own_clock(cell, monkeypatch):
    seen = {}
    real = run.Ctx.__init__

    def keep(self, **kw):
        real(self, **kw)
        seen["ctx"] = self
    monkeypatch.setattr(run.Ctx, "__init__", keep)
    spec = run.Spec(cell)
    code, result = run.run_cell(cell, 11, 3, 1, look_for_chip=False,
                                scale=rehearse.TOY[spec.config["datagen"]],
                                fault=CELLS[cell])
    assert result["correct"] and code == 0, result["checks"]
    ctx, got = seen["ctx"], result["metrics"]
    # every family that rose belongs to one row, or to the write path
    rows = {s: m for m, row in stages.ROWS.items() for s in row}
    rose = {stages.stage_of(f) for f in ctx.m1
            if stages.stage_of(f) and ctx.delta(f) > 0}
    assert rose and rose <= set(rows) | set(stages.WRITE_PATH), rose
    assert {"query", "execute", "select-series", "encode",
            "device-dispatch", "device-sync"} <= rose
    # every new reader returns a number in both cells
    for name in NEW:
        assert got[name]["value"] > 0, name
    assert got["host_cpu_share"]["value"] < 150.0    # a sampled estimate
    # the rows (admission-wait is taken before the root opens) add up to
    # what server_query_ms reads, the node's own clock per query
    n = ctx.delta(stages.QUERIES)
    adm = ctx.delta(stages.family("admission-wait",
                                  "self_seconds_total")) / n * 1e3
    total = sum(got[m]["value"] for m in stages.ROWS) - adm
    assert total == pytest.approx(got["server_query_ms"]["value"], rel=0.10)
    assert got["unattributed_ms"]["value"] \
        < 0.25 * got["server_query_ms"]["value"]
