"""``plan_facts_hit_share`` at a toy size on the CPU: the 128-shard cell sends
one template over a store nobody writes to, so after the warm-up every mesh
lowering takes "no histogram" from the selection memo's entry (a high share)
and ``parse_plan_ms`` no longer holds a walk over every partition; a node
without a mesh never asks, so the other cells do not list the metric; a
program without the two counters gives ``None`` and the metric is left out of
the line.

    python -m pytest benchmarks/tests/test_plan_facts_hit_share.py -q
"""

import pytest

import run

NAME = "plan_facts_hit_share"
CELL = "shards128.mesh-sumby"
TOY = {"apps": 8, "jobs": 4, "instances": 8}
HITS = "filodb_plan_selection_facts_hits_total"
WALKS = "filodb_plan_selection_facts_walks_total"


def _ctx(hits, walks, before=0.0):
    return run.Ctx(m0={HITS: before, WALKS: before},
                   m1={HITS: before + hits, WALKS: before + walks})


@pytest.mark.parametrize("hits, walks, want", [
    (2400, 0, 100.0), (0, 2400, 0.0), (300, 100, 75.0), (0, 0, None)],
    ids=["hits-only", "walks-only", "both", "neither"])
def test_reader(hits, walks, want):
    read = run.load_module("layers", NAME).read
    assert read(_ctx(hits, walks, before=7.0)) == want


def test_program_without_the_counters_reads_nothing():
    read = run.load_module("layers", NAME).read
    assert read(run.Ctx(m0={}, m1={"filodb_select_memo_hits_total": 3.0})) \
        is None


def test_the_mesh_cell_alone_lists_it():
    bench = run.load_json(run.os.path.join(run.ROOT, "BENCHMARK.json"))
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter",
                     "layer": "HTTP + parse/plan + engine",
                     "moves": "query_p50_ms", "workloads": [CELL]}
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.Spec(cell["name"]).metrics("per_layer")]
        assert (NAME in names) == (cell["name"] == CELL), cell["name"]


def test_cell_reports_it(monkeypatch):
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    code, result = run.run_cell(CELL, 3700000013, 3, 1, look_for_chip=False,
                                scale=TOY)
    assert result["correct"] and code == 0, result["checks"]
    m = result["metrics"]
    assert m[NAME]["unit"] == "%"
    # one template, a read-only store: at most the window's first sights walk
    assert m[NAME]["value"] >= 99.0
    assert m["mesh_share"]["value"] == 100.0
    assert m["select_memo_hit_share"]["value"] == 100.0
