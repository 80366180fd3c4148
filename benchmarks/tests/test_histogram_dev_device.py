"""The admitted histogram cell ``histogram-dev.p99-by-job-device`` end to end
at its configuration's toy size on the CPU, its fused quantile program taken
in interpret mode (``fault="fused_interpret"``, as the program's own tests
take it): correct, every answer from the device program; its controls and a
fault not correct; without the switch a CPU node serves the host path, which
``must_rise`` does not count; and the candidate it grew from still resolves
to its own traffic.

    python -m pytest benchmarks/tests/test_histogram_dev_device.py -q
"""

import pytest

import rehearse
import run

CELL = "histogram-dev.p99-by-job-device"
CANDIDATE = "histogram-dev.p99-by-job"
SEED = 3900000017


def _toy_run(trace=0, fault="fused_interpret", **kw):
    spec = run.Spec(CELL)
    return run.run_cell(CELL, SEED, 3, trace, look_for_chip=False,
                        scale=rehearse.toy_scale(spec.config), fault=fault,
                        **kw)


def test_cell_resolves_to_its_traffic_and_the_candidate_to_its_own():
    spec = run.Spec(CELL)
    assert spec.cell["traffic"] == "p99-by-job-device"
    assert spec.cell["chips"] == 1 and spec.config["name"] == "histogram-dev"
    assert spec.workload["must_rise"] == ["filodb_fused_hist_aggs_total"]
    old = run.Spec(CANDIDATE, CANDIDATE)
    assert old.cell["traffic"] == "p99-by-job" and old.workload["must_rise"] \
        == []
    # one traffic but for what decides `correct`
    strip = ("must_rise", "check")
    assert {k: v for k, v in spec.workload.items() if k not in strip} == \
        {k: v for k, v in old.workload.items() if k not in strip}
    assert spec.workload["check"]["sample"] == old.workload["check"]["sample"]


def test_cell_reports_its_metrics():
    spec = run.Spec(CELL)
    names = {m["name"] for m in spec.metrics("per_layer")}
    assert {"hist_fused_share", "hist_quantile_roofline",
            "tile_hit_share", "window_compiles"} <= names
    assert not names & {"fused_share", "holes_fused_share",
                        "gap_refusal_share", "sumby_roofline"}
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "query_p50_ms", "queries_per_s", "setup_s"}


def test_traced_rehearsal_is_correct_and_fused():
    code, result = _toy_run(trace=1)
    assert result["correct"] and code == 0, result["checks"]
    assert result["failed"] == 0
    got = result["metrics"]
    assert got["hist_fused_share"]["value"] == 100.0
    assert "tile_hit_share" in got
    assert got["window_compiles"]["value"] == 0.0
    # a CPU trace holds no device-busy time: the roofline reads nothing
    assert "hist_quantile_roofline" not in got


@pytest.mark.parametrize("kw", [{"control": "bf16"}, {"control": "stale"},
                                {"fault": "fused_interpret,alter_answer"}])
def test_control_and_fault_are_not_correct(kw):
    code, result = _toy_run(**kw)
    c = result["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
    assert not result["correct"] and code != 0


def test_host_path_is_not_correct():
    """A CPU node without the switch serves every request on the host:
    the answers agree, and the cell is not correct, since the device
    family did not rise."""
    code, result = _toy_run(fault=None)
    checks = result["checks"]
    assert checks["max_rel_err"]["value"] <= checks["max_rel_err"]["limit"]
    assert checks["device_counters_rose"]["value"] == 0
    assert not result["correct"] and code != 0


def test_readers_read_nothing_without_their_source():
    share = run.load_module("layers", "hist_fused_share").read
    roof = run.load_module("layers", "hist_quantile_roofline").read
    fam = "filodb_fused_hist_aggs_total"
    assert share(run.Ctx(ok=[1], m0={}, m1={})) is None
    assert share(run.Ctx(ok=[1, 2], m0={fam: 3.0}, m1={fam: 4.0})) == 50.0
    assert share(run.Ctx(ok=[1], m0={fam: 3.0}, m1={fam: 3.0})) == 0.0
    for trace in (None, {"busy_s": None}, {"busy_s": 0.0}):
        assert roof(run.Ctx(trace=trace)) is None
