"""``shards128-4chip`` and its cell ``shards128.mesh-sumby``: the cell end to
end at a toy size on four virtual CPU devices (a CPU node with more than one
device and ``mesh-enabled`` takes the mesh path: the grouped collective is
plain XLA), its controls, and the four readers this cell brings against
hand-made contexts.

    python -m pytest benchmarks/tests/test_shards128_mesh.py -q
"""

import pytest

import run

CELL = "shards128.mesh-sumby"
TOY = {"apps": 8, "jobs": 4, "instances": 8}
SEED = 3400000007
MINE = ("mesh_share", "mesh_refusal_share", "mesh_sumby_roofline",
        "mesh_place_ms")


@pytest.fixture(autouse=True)
def four_devices(monkeypatch):
    """The node child inherits the environment: four virtual devices."""
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")


def test_the_generator_refuses_a_program_whose_idle_drivers_poll(tmp_path,
                                                                monkeypatch):
    """The samples are ``promperf_counters``'s; the one thing added is a
    look at the program, so that a commit that cannot run 128 shards inside
    the time limit ends its run with an error and is not killed."""
    import importlib
    gen = importlib.import_module("datagen.shards128_counters")
    plain = importlib.import_module("datagen.promperf_counters")
    spec = run.Spec(CELL)
    assert spec.config["datagen"] == "shards128_counters"
    assert gen.can_run_128_shards()             # this checkout's program
    a, b = gen.make(spec.config, SEED, TOY), plain.make(spec.config, SEED, TOY)
    assert (a.ts == b.ts).all() and (a.vals == b.vals).all()
    assert a.labels == b.labels and a.n_hist == b.n_hist
    assert not gen.can_run_128_shards(str(tmp_path))        # no program
    old = tmp_path / "filodb_tpu" / "ingest"
    old.mkdir(parents=True)
    (old / "driver.py").write_text("self._stop.wait(self.poll_interval_s)\n")
    assert not gen.can_run_128_shards(str(tmp_path))
    monkeypatch.setattr(gen, "ROOT", str(tmp_path))
    with pytest.raises(RuntimeError, match="128-shard"):
        gen.make(spec.config, SEED, TOY)


def _toy_run(trace=0, **kw):
    return run.run_cell(CELL, SEED, 3, trace, look_for_chip=False, scale=TOY,
                        **kw)


def test_the_cell_is_the_issues():
    spec = run.Spec(CELL)
    assert spec.cell["chips"] == 4 and spec.cell["config"] == "shards128-4chip"
    d, node = spec.config["data"], spec.config["node"]
    assert d["apps"] * d["jobs"] * d["instances"] == 24576
    assert node["num-shards"] == 128 and node["mesh-enabled"] is True
    w = spec.workload
    tmpl, = w["queries"]
    assert w["clients"] == 4 and w["scrape"] is None
    assert tmpl["query"]["select"] == {"_ws_": "$ws"}
    assert (tmpl["range_s"], tmpl["step_s"], tmpl["end"]) \
        == (1800, 60, "history")
    assert w["check"] == {"sample": 12, "limits": {"max_rel_err": 1e-05}}
    assert w["must_rise"] == ["filodb_mesh_dispatches_total"]
    e2e = {m["name"] for m in spec.metrics("end_to_end")}
    assert e2e == {"query_p50_ms", "queries_per_s", "setup_s"}
    layers = {m["name"] for m in spec.metrics("per_layer")}
    assert set(MINE) <= layers
    assert not layers & {"sumby_roofline", "batch_occupancy",
                         "gap_refusal_share", "holes_fused_share"}
    # and no other cell reports this cell's four
    for other in run.Spec(CELL).bench["workloads"]:
        if other["name"] != CELL:
            names = {m["name"]
                     for m in run.Spec(other["name"]).metrics("per_layer")}
            assert not names & set(MINE), other["name"]


def test_cell_end_to_end():
    code, result = _toy_run(trace=1)
    assert result["correct"] and code == 0, result["checks"]
    assert result["failed"] == 0 and result["device"]["count"] == 4
    assert result["checks"]["answers_compared"]["value"] == 12
    m = result["metrics"]
    assert m["mesh_share"] == {"value": 100.0, "unit": "%"}
    assert m["mesh_refusal_share"] == {"value": 0.0, "unit": "%"}
    assert m["mesh_place_ms"] == {"value": 0.0, "unit": "ms"}
    assert "mesh_sumby_roofline" not in m       # nothing ran on a device
    assert m["fused_share"]["value"] == 100.0
    assert m["tile_hit_share"]["value"] == 100.0
    assert m["select_memo_hit_share"]["value"] == 100.0
    assert m["select_read_share"]["value"] == 0.0
    assert m["window_compiles"]["value"] == 0.0
    # two [T, G] float64 grids a query: 2 x 31 x 4 x 8 B
    assert m["d2h_kb_per_query"]["value"] == pytest.approx(1.984)
    assert result["phases_s"]["warmup_requests"] >= 2


def test_on_one_device_the_single_chip_path_serves_and_it_is_not_correct(
        monkeypatch):
    """``must_rise`` is the mesh's counter: a run that one chip served is
    not this cell's."""
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=1")
    code, result = _toy_run(trace=1, fault="fused_interpret")
    c = result["checks"]
    assert c["max_rel_err"]["value"] <= c["max_rel_err"]["limit"]
    assert c["device_counters_rose"]["value"] == 0
    assert not result["correct"] and code != 0
    assert result["metrics"]["mesh_share"]["value"] == 0.0
    assert result["metrics"]["fused_share"]["value"] == 100.0


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
    world = datagen.make(spec.config, 11, TOY)
    q = {**spec.workload["queries"][0]["query"], "select": {"_ws_": "demo"}}
    ok = [_Done(_Req(q, 1700003000, 1700004800)) for _ in range(n_ok)]
    return run.Ctx(
        ok=ok, world=world, device={"kind": "TPU v5 lite"},
        peaks={"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
        trace={"busy_s": busy_s, "device_planes": planes, "window_s": 51.0})


def test_mesh_sumby_roofline_is_a_quarter_of_sumby_rooflines_on_four_planes():
    mesh = run.load_module("layers", "mesh_sumby_roofline")
    one = run.load_module("layers", "sumby_roofline")
    ctx = _ctx(planes=4, busy_s=2e-3)
    # 5 answers x 256 series x 211 slots x 12 B over 4 x 819 GB/s, over 2 ms
    want = 100.0 * (5 * 256 * 211 * 12 / (4 * 819e9)) / 2e-3
    assert mesh.read(ctx) == pytest.approx(want)
    assert mesh.read(ctx) == pytest.approx(one.read(ctx) / 4)
    assert mesh.read(_ctx(planes=1, busy_s=2e-3)) \
        == pytest.approx(one.read(ctx))


@pytest.mark.parametrize("trace", [
    None, {"busy_s": None, "device_planes": 0},
    {"busy_s": 0.0, "device_planes": 4}])
def test_mesh_sumby_roofline_reads_nothing_where_nothing_ran(trace):
    ctx = _ctx(planes=4, busy_s=1.0)
    ctx.trace = trace
    assert run.load_module("layers", "mesh_sumby_roofline").read(ctx) is None


def test_mesh_sumby_roofline_refuses_a_device_it_has_no_peak_for():
    ctx = _ctx(planes=4, busy_s=1.0)
    ctx.device = {"kind": "cpu"}
    with pytest.raises(KeyError):
        run.load_module("layers", "mesh_sumby_roofline").read(ctx)


PLACE = "filodb_stage_mesh_place_self_seconds_total"
QUERIES = "filodb_query_latency_seconds_count"


@pytest.mark.parametrize("name,m0,m1,want", [
    ("mesh_share", {"filodb_mesh_dispatches_total": 2.0},
     {"filodb_mesh_dispatches_total": 5.0}, 75.0),
    ("mesh_share", {}, {"filodb_mesh_dispatches_total": 0.0}, 0.0),
    ("mesh_refusal_share", {"filodb_mesh_refused_total": 1.0},
     {"filodb_mesh_refused_total": 2.0}, 25.0),
    ("mesh_refusal_share", {}, {"filodb_mesh_refused_total": 0.0}, 0.0),
    ("mesh_place_ms", {PLACE: 1.0, QUERIES: 10.0},
     {PLACE: 1.5, QUERIES: 14.0}, 125.0),
    ("mesh_place_ms", {PLACE: 1.0, QUERIES: 10.0},
     {PLACE: 1.0, QUERIES: 14.0}, 0.0),
])
def test_counter_readers(name, m0, m1, want):
    ctx = run.Ctx(ok=[object()] * 4, m0=m0, m1=m1)
    assert run.load_module("layers", name).read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", ["mesh_share", "mesh_refusal_share",
                                  "mesh_place_ms"])
def test_readers_find_nothing_on_a_program_without_the_counters(name):
    """The parent commit exports none of the new families: the reader
    returns nothing and does not raise."""
    ctx = run.Ctx(ok=[object()], m0={}, m1={QUERIES: 3.0,
                                            "filodb_fused_aggs_total": 3.0})
    if name == "mesh_share":
        ctx.m1 = {QUERIES: 3.0}
    assert run.load_module("layers", name).read(ctx) is None
    assert run.load_module("layers", name).read(
        run.Ctx(ok=[], m0={}, m1=ctx.m1)) is None
