"""``packed_h2d_arrays_per_query`` at a toy size on the CPU: a packed launch
hands the device two host arrays (one int64 and one f64 block) whatever its
batch's size, so cell 2, where every request takes the packed path, reads
about 2 over its ``batch_occupancy``; a program without the counter gives
``None`` and the metric is left out of the line.

    python -m pytest benchmarks/tests/test_packed_h2d_arrays_per_query.py -q
"""

import pytest

import rehearse
import run

NAME = "packed_h2d_arrays_per_query"
ARRAYS = "filodb_packed_host_arrays_total"
CELLS = ["tsbs-devops.host-dashboards"]


@pytest.mark.parametrize("arrays, answered, want", [
    (2000, 1000, 2.0), (1100, 1000, 1.1), (0, 10, 0.0), (7, 4, 1.75)],
    ids=["lone", "batched", "none-launched", "mixed"])
def test_reader(arrays, answered, want):
    read = run.load_module("layers", NAME).read
    ctx = run.Ctx(m0={ARRAYS: 41.0}, m1={ARRAYS: 41.0 + arrays},
                  ok=[None] * answered)
    assert read(ctx) == want


def test_program_without_the_counter_reads_nothing():
    read = run.load_module("layers", NAME).read
    assert read(run.Ctx(m0={}, m1={"filodb_host_to_device_puts_total": 3.0},
                        ok=[None] * 5)) is None
    assert read(run.Ctx(m0={ARRAYS: 0.0}, m1={ARRAYS: 4.0}, ok=[])) is None


def test_the_gauge_cell_lists_it():
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter",
                     "layer": "backend dispatch",
                     "moves": "query_p50_ms", "workloads": CELLS}
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.Spec(cell["name"]).metrics("per_layer")]
        assert (NAME in names) == (cell["name"] in CELLS), cell["name"]


def test_the_gauge_cell_reads_two_a_launch():
    spec = run.Spec(CELLS[0])
    code, result = run.run_cell(CELLS[0], 4300000013, 3, 1,
                                look_for_chip=False,
                                scale=rehearse.toy_scale(spec.config))
    assert result["correct"] and code == 0, result["checks"]
    m = result["metrics"]
    assert m[NAME]["unit"] == "count"
    # two arrays a launch, one launch a batch (requests in flight at the
    # window's edges move the ratio by a request's share)
    assert m[NAME]["value"] == pytest.approx(
        2.0 / m["batch_occupancy"]["value"], rel=0.05)
