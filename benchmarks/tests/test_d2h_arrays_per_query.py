"""``d2h_arrays_per_query`` at a toy size on the CPU: each fused program
returns its answer as one array, so a request of the five fused cells brings
one array to the host (the packed batch of ``tsbs-devops.host-dashboards``
shares one among its members, and that cell does not list the metric); a
program without the counter gives ``None`` and the metric is left out of the
line.

    python -m pytest benchmarks/tests/test_d2h_arrays_per_query.py -q
"""

import pytest

import run

NAME = "d2h_arrays_per_query"
ARRAYS = "filodb_device_to_host_arrays_total"
CELLS = ["promperf.history-sumby", "promperf-missed-scrapes.history-sumby",
         "shards128.mesh-sumby", "histogram-dev.p99-by-job-device",
         "histogram128.mesh-p99-by-job"]
TOY = {"apps": 8, "jobs": 4, "instances": 8}


@pytest.mark.parametrize("arrays, answered, want", [
    (1000, 1000, 1.0), (2000, 1000, 2.0), (0, 10, 0.0), (15, 10, 1.5)],
    ids=["one-array", "two-arrays", "none-synced", "mixed"])
def test_reader(arrays, answered, want):
    read = run.load_module("layers", NAME).read
    ctx = run.Ctx(m0={ARRAYS: 77.0}, m1={ARRAYS: 77.0 + arrays},
                  ok=[None] * answered)
    assert read(ctx) == want


def test_program_without_the_counter_reads_nothing():
    read = run.load_module("layers", NAME).read
    assert read(run.Ctx(m0={}, m1={"filodb_device_to_host_bytes_total": 3.0},
                        ok=[None] * 5)) is None
    assert read(run.Ctx(m0={ARRAYS: 0.0}, m1={ARRAYS: 4.0}, ok=[])) is None


def test_the_five_fused_cells_list_it():
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter", "layer": "device",
                     "moves": "query_p50_ms", "workloads": CELLS}
    assert bench["per_layer"][-1] == entry
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.Spec(cell["name"]).metrics("per_layer")]
        assert (NAME in names) == (cell["name"] in CELLS), cell["name"]


def test_the_mesh_cell_reads_one(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    code, result = run.run_cell(CELLS[2], 4200000013, 3, 1,
                                look_for_chip=False, scale=TOY)
    assert result["correct"] and code == 0, result["checks"]
    m = result["metrics"]
    assert m["mesh_share"]["value"] == 100.0
    assert m[NAME]["unit"] == "count"
    # one array a request (requests in flight at the window's edges move
    # the ratio by a request's share)
    assert m[NAME]["value"] == pytest.approx(1.0, rel=0.02)
