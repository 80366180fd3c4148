"""``histogram-128shards-4chip`` and its cell ``histogram128.mesh-p99-by-job``:
the cell end to end at its configuration's toy size on four virtual CPU
devices (a CPU node with more than one device and ``mesh-enabled`` takes the
mesh store's histogram program: its collective is plain XLA), its controls,
the run one chip serves, and the reader this cell brings against hand-made
contexts. The rehearsals merge in ``candidates/<cell>.json``: the three mesh
rows that ``test_shards128_mesh.py`` keeps to cell 4 for now.

    python -m pytest benchmarks/tests/test_histogram_mesh.py -q
"""

import pytest

import rehearse
import run

CELL = "histogram128.mesh-p99-by-job"
SEED = 4100000123
READER = "mesh_hist_quantile_roofline"
HELD = ("mesh_share", "mesh_refusal_share", "mesh_place_ms")


@pytest.fixture(autouse=True)
def four_devices(monkeypatch):
    """The node child inherits the environment: four virtual devices."""
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")


def _toy_run(trace=0, **kw):
    spec = run.Spec(CELL)
    return run.run_cell(CELL, SEED, 3, trace, look_for_chip=False,
                        scale=rehearse.toy_scale(spec.config),
                        candidate=CELL, **kw)


def test_the_cell_is_the_issues():
    spec = run.Spec(CELL)
    assert spec.cell["chips"] == 4
    assert spec.cell["config"] == "histogram-128shards-4chip"
    d, node = spec.config["data"], spec.config["node"]
    assert (d["apps"], d["jobs"], d["instances"]) == (48, 16, 8)
    assert node["num-shards"] == 128 and node["mesh-enabled"] is True
    assert node["query-sample-limit"] == 16_000_000
    # histogram-dev's data block to the letter, but for the series count
    dev = run.Spec("histogram-dev.p99-by-job-device").config["data"]
    counts = ("apps", "instances")
    assert {k: v for k, v in d.items() if k not in counts} \
        == {k: v for k, v in dev.items() if k not in counts}
    assert spec.config["toy"] == {"apps": 3, "jobs": 4, "instances": 8}
    w = spec.workload
    tmpl, = w["queries"]
    assert w["clients"] == 4 and w["scrape"] is None
    assert tmpl["query"]["select"] == {"_ws_": "$ws"}
    assert tmpl["query"]["quantile"] == 0.99
    assert (tmpl["range_s"], tmpl["step_s"], tmpl["end"]) \
        == (1800, 60, "history")
    assert w["warmup"]["each"] == ["ws"]
    assert w["check"] == {"sample": 12, "limits": {"max_rel_err": 1e-09}}
    assert w["must_rise"] == ["filodb_fused_hist_aggs_total",
                              "filodb_mesh_dispatches_total"]


def test_the_cell_reports_the_mesh_and_histogram_metrics_and_not_the_rest():
    spec = run.Spec(CELL)
    assert {m["name"] for m in spec.metrics("end_to_end")} \
        == {"query_p50_ms", "queries_per_s", "setup_s"}
    layers = {m["name"] for m in spec.metrics("per_layer")}
    assert {READER, "hist_fused_share", "tile_hit_share",
            "d2h_kb_per_query", "dispatch_host_ms",
            "selection_facts_hit_share", "interpreter_busy_share",
            "window_compiles"} <= layers
    assert not layers & {"h2d_puts_per_query", "plan_facts_hit_share",
                         "fused_share", "sumby_roofline",
                         "mesh_sumby_roofline", "hist_quantile_roofline",
                         *HELD}
    with_held = {m["name"]
                 for m in run.Spec(CELL, CELL).metrics("per_layer")}
    assert with_held == layers | set(HELD)
    for other in spec.bench["workloads"]:
        if other["name"] != CELL:
            names = {m["name"]
                     for m in run.Spec(other["name"]).metrics("per_layer")}
            assert READER not in names, other["name"]


def test_cell_end_to_end():
    code, result = _toy_run(trace=1)
    assert result["correct"] and code == 0, result["checks"]
    assert result["failed"] == 0 and result["device"]["count"] == 4
    assert result["checks"]["answers_compared"]["value"] == 12
    m = result["metrics"]
    assert m["mesh_share"] == {"value": 100.0, "unit": "%"}
    assert m["hist_fused_share"]["value"] == 100.0
    assert m["mesh_refusal_share"] == {"value": 0.0, "unit": "%"}
    assert m["mesh_place_ms"] == {"value": 0.0, "unit": "ms"}
    assert m["tile_hit_share"]["value"] == 100.0
    assert m["window_compiles"]["value"] == 0.0
    assert READER not in m                      # nothing ran on a device
    # the [T, G, B] float64 bucket sums a query: 31 x 4 x 12 x 8 B
    assert m["d2h_kb_per_query"]["value"] == pytest.approx(11.904)
    assert result["phases_s"]["warmup_requests"] >= 2


def test_on_one_device_one_chip_serves_and_it_is_not_correct(monkeypatch):
    """``must_rise`` names the mesh's counter: a run that the one-chip
    program served answers as well and is not this cell's."""
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    code, result = _toy_run(fault="fused_interpret")
    c = result["checks"]
    assert c["max_rel_err"]["value"] <= c["max_rel_err"]["limit"]
    assert c["device_counters_rose"]["value"] == 0
    assert not result["correct"] and code != 0


@pytest.mark.parametrize("control", ["bf16", "stale"])
def test_control_is_not_correct(control):
    code, result = _toy_run(control=control)
    c = result["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
    assert not result["correct"] and code != 0


class _Req:
    def __init__(self, query, start_s, end_s):
        self.query, self.start_s, self.end_s = query, start_s, end_s


class _Done:
    def __init__(self, req):
        self.req = req


def _ctx(planes, busy_s, n_ok=5):
    import importlib
    spec = run.Spec(CELL)
    datagen = importlib.import_module("datagen." + spec.config["datagen"])
    world = datagen.make(spec.config, 11, rehearse.toy_scale(spec.config))
    q = {**spec.workload["queries"][0]["query"], "select": {"_ws_": "demo"}}
    ok = [_Done(_Req(q, 1700003000, 1700004800)) for _ in range(n_ok)]
    return run.Ctx(
        ok=ok, world=world, device={"kind": "TPU v5 lite"},
        peaks={"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
        trace={"busy_s": busy_s, "device_planes": planes, "window_s": 51.0})


def test_the_reader_is_a_quarter_of_the_one_chip_reading_on_four_planes():
    mesh = run.load_module("layers", READER)
    one = run.load_module("layers", "hist_quantile_roofline")
    ctx = _ctx(planes=4, busy_s=2e-3)
    # 5 answers x 96 series x 211 slots x (12 x 8 + 4) B over 4 x 819 GB/s,
    # over 2 ms
    want = 100.0 * (5 * 96 * 211 * 100 / (4 * 819e9)) / 2e-3
    assert mesh.read(ctx) == pytest.approx(want)
    assert mesh.read(ctx) == pytest.approx(one.read(ctx) / 4)
    assert mesh.read(_ctx(planes=1, busy_s=2e-3)) \
        == pytest.approx(one.read(ctx))


@pytest.mark.parametrize("trace", [
    None, {"busy_s": None, "device_planes": 0},
    {"busy_s": 0.0, "device_planes": 4}])
def test_the_reader_reads_nothing_where_nothing_ran(trace):
    ctx = _ctx(planes=4, busy_s=1.0)
    ctx.trace = trace
    assert run.load_module("layers", READER).read(ctx) is None


def test_the_reader_refuses_a_device_it_has_no_peak_for():
    ctx = _ctx(planes=4, busy_s=1.0)
    ctx.device = {"kind": "cpu"}
    with pytest.raises(KeyError):
        run.load_module("layers", READER).read(ctx)
