"""The five readers of what the interpreter itself costs a request
(``interpreter_busy_share``, ``interpreter_wait_ms``, ``gc_pause_ms``,
``gc_stalls``, ``stage_gc_share``): each on made-up scrapes (a value, the 0
reading, and ``None`` on a program without the family), then in the line of a
toy cell on the CPU.

    python -m pytest benchmarks/tests/test_interpreter_readers.py -q
"""

import pytest

import rehearse
import run
import stages

NEW = ("interpreter_busy_share", "interpreter_wait_ms", "gc_pause_ms",
       "gc_stalls", "stage_gc_share")
COUNT = "filodb_interpreter_wait_seconds_count"
SUM = "filodb_interpreter_wait_seconds_sum"
WAITED = "filodb_interpreter_probes_waited_total"
PAUSE = "filodb_gc_pause_seconds_total"
STALLS = "filodb_gc_stalls_total"
# a program of the parent's kind: stage counters, no collector column
OLD = {stages.QUERIES: 500.0,
       stages.family("execute", "cpu_seconds_total"): 2.0,
       stages.family("execute", "self_seconds_total"): 4.0}


def _read(name, rose, before=3.0):
    m0 = {f: before for f in rose}
    m1 = {f: before + d for f, d in rose.items()}
    return run.load_module("layers", name).read(run.Ctx(m0=m0, m1=m1))


@pytest.mark.parametrize("name, rose, want", [
    ("interpreter_busy_share", {COUNT: 5000, WAITED: 4500}, 90.0),
    ("interpreter_busy_share", {COUNT: 5000, WAITED: 0}, 0.0),
    ("interpreter_busy_share", {COUNT: 0, WAITED: 0}, None),
    ("interpreter_wait_ms", {COUNT: 4000, SUM: 10.0}, 2.5),
    ("interpreter_wait_ms", {COUNT: 4000, SUM: 0.0}, 0.0),
    ("interpreter_wait_ms", {COUNT: 0, SUM: 0.0}, None),
    # the harness sums the generations' label sets into the family
    ("gc_pause_ms", {PAUSE: 1.5, stages.QUERIES: 2000}, 0.75),
    ("gc_pause_ms", {PAUSE: 0.0, stages.QUERIES: 2000}, 0.0),
    ("gc_pause_ms", {PAUSE: 1.5, stages.QUERIES: 0}, None),
    ("gc_stalls", {STALLS: 2}, 2.0),
    ("gc_stalls", {STALLS: 0}, 0.0),
    ("stage_gc_share", {
        stages.family("execute", "gc_seconds_total"): 0.25,
        stages.family("encode", "gc_seconds_total"): 0.25,
        stages.family("execute", "cpu_seconds_total"): 3.0,
        stages.family("encode", "cpu_seconds_total"): 1.0,
        # the waits are out, on both sides
        stages.family("device-sync", "gc_seconds_total"): 9.0,
        stages.family("device-sync", "cpu_seconds_total"): 9.0,
        stages.family("query", "gc_seconds_total"): 0.0}, 12.5),
    ("stage_gc_share", {
        stages.family("query", "gc_seconds_total"): 0.0,
        stages.family("execute", "cpu_seconds_total"): 3.0}, 0.0),
    ("stage_gc_share", {
        stages.family("query", "gc_seconds_total"): 0.0,
        stages.family("execute", "cpu_seconds_total"): 0.0}, None),
])
def test_reader(name, rose, want):
    assert _read(name, rose) == want


@pytest.mark.parametrize("name", NEW)
def test_program_without_the_family_reads_nothing(name):
    assert _read(name, OLD) is None


@pytest.mark.parametrize("name", NEW)
def test_entry_lists_all_four_cells(name):
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "HTTP + parse/plan + engine"
    assert entry["better"] == "lower"


def test_cell_reports_all_five():
    cell = "tsbs-devops.host-dashboards"
    spec = run.Spec(cell)
    assert set(NEW) <= {m["name"] for m in spec.metrics("per_layer")}
    code, result = run.run_cell(cell, 17, 3, 1, look_for_chip=False,
                                scale=rehearse.TOY[spec.config["datagen"]])
    assert result["correct"] and code == 0, result["checks"]
    got = result["metrics"]
    assert set(NEW) <= set(got)
    assert 0.0 <= got["interpreter_busy_share"]["value"] <= 100.0
    assert got["interpreter_wait_ms"]["value"] >= 0.0
    assert got["gc_pause_ms"]["value"] >= 0.0
    assert got["gc_stalls"]["value"] >= 0.0
    assert 0.0 <= got["stage_gc_share"]["value"]
    assert [got[n]["unit"] for n in NEW] == ["%", "ms", "ms", "count", "%"]
