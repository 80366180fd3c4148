"""``h2d_puts_per_query`` at a toy size on the CPU: the four fused cells hand
the device one host array a request (the grid), which the mesh cell puts on
each of its four devices; a program without the counter gives ``None`` and
the metric is left out of the line.

    python -m pytest benchmarks/tests/test_h2d_puts_per_query.py -q
"""

import pytest

import run

NAME = "h2d_puts_per_query"
PUTS = "filodb_host_to_device_puts_total"
CELLS = ["promperf.history-sumby", "promperf-missed-scrapes.history-sumby",
         "shards128.mesh-sumby", "histogram-dev.p99-by-job-device"]
TOY = {"apps": 8, "jobs": 4, "instances": 8}


@pytest.mark.parametrize("puts, answered, want", [
    (4000, 1000, 4.0), (1000, 1000, 1.0), (0, 10, 0.0), (35, 10, 3.5)],
    ids=["mesh", "one-chip", "none-put", "mixed"])
def test_reader(puts, answered, want):
    read = run.load_module("layers", NAME).read
    ctx = run.Ctx(m0={PUTS: 123.0}, m1={PUTS: 123.0 + puts},
                  ok=[None] * answered)
    assert read(ctx) == want


def test_program_without_the_counter_reads_nothing():
    read = run.load_module("layers", NAME).read
    assert read(run.Ctx(m0={}, m1={"filodb_fused_aggs_total": 3.0},
                        ok=[None] * 5)) is None
    assert read(run.Ctx(m0={PUTS: 0.0}, m1={PUTS: 4.0}, ok=[])) is None


def test_the_four_fused_cells_list_it():
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter",
                     "layer": "backend dispatch",
                     "moves": "query_p50_ms", "workloads": CELLS}
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.Spec(cell["name"]).metrics("per_layer")]
        assert (NAME in names) == (cell["name"] in CELLS), cell["name"]


def test_the_mesh_cell_reads_four(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    code, result = run.run_cell(CELLS[2], 4000000013, 3, 1,
                                look_for_chip=False, scale=TOY)
    assert result["correct"] and code == 0, result["checks"]
    m = result["metrics"]
    assert m["mesh_share"]["value"] == 100.0
    assert m[NAME]["unit"] == "count"
    # one grid a request on four devices (requests in flight at the
    # window's edges move the ratio by a request's share)
    assert m[NAME]["value"] == pytest.approx(4.0, rel=0.02)
