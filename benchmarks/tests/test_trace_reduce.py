"""The trace reducer: on intervals made by hand, and on the device planes of
a trace recorded on the chip (``fixtures/``, the head of every line of this
PR's first traced run of ``promperf.history-sumby``)."""

import glob
import os

import pytest

import trace_reduce

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


def test_union_and_gaps_by_hand():
    ev = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_f", 0, 1000]]},
        {"name": "XLA Ops", "events": [
            ["a", 0, 100], ["b", 50, 100],          # overlap: 0..150
            ["c", 400, 100],                        # gap 250 after b
            ["a", 1000, 50]]}]}]}                   # gap 500 after c
    r = trace_reduce.reduce(ev)
    assert r["device_planes"] == 1 and r["n_ops"] == 4
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["span_s"] == pytest.approx(1050e-9)
    assert r["device_ops"][0] == ["a", pytest.approx(150e-9)]
    assert r["idle_gaps"][0] == ["after c before a", pytest.approx(500e-9)]
    assert r["idle_gaps"][1] == ["after b before c", pytest.approx(250e-9)]


def test_no_device_plane_reads_nothing():
    r = trace_reduce.reduce({"planes": []})
    assert r["busy_s"] is None and r["device_ops"] == []


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(FIXTURES, "*.json.gz"))) or [None])
def test_recorded_trace(path):
    if path is None:
        pytest.skip("no recorded trace under fixtures/")
    ev = trace_reduce.load_events(path)
    r = trace_reduce.reduce(ev)
    assert r["device_planes"] >= 1 and r["n_ops"] > 0
    # busy time is a union: never more than the span, never more than the sum
    ops = [e for p in ev["planes"] for e in trace_reduce.op_events(p)]
    assert 0 < r["busy_s"] <= r["span_s"]
    assert r["busy_s"] <= sum(e[2] for e in ops) / 1e9 + 1e-12
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in r["device_ops"])
    # the breakdown is sorted, largest first
    secs = [s for _, s in r["device_ops"]]
    assert secs == sorted(secs, reverse=True)
