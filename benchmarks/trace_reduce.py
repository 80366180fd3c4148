"""From the profiler's trace to device numbers: the yardstick's own reduction.

``load_xplane`` reads an ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``), so it runs only in the node child, and keeps
the DEVICE planes' events. ``reduce`` is plain Python over those events and
runs anywhere (``tests/test_trace_reduce.py`` checks it against
``fixtures/``): busy time is the union of the intervals in which an operation
ran on a device, averaged over the devices; the breakdown names the
operations that took most time and the longest idle gaps.

The program puts no spans of its own on the profiler's clock yet, so an idle
gap is named by the device operations that bracket it; what the host was
doing in it is for the ``tracing`` issue (PERF.md section 7).
"""

import gzip
import json

OPS_LINE = "XLA Ops"
NAME_CHARS = 120               # the trace names an op by its whole HLO text


def load_xplane(path):
    """-> {"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, dur_ns], ..]}]}]} for the device planes of the trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes, seen = [], []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            seen.append([plane.name, len(list(plane.lines)), None])
            continue
        lines = []
        for line in plane.lines:
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events]
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
        seen += [[plane.name, l["name"], len(l["events"])] for l in lines]
    return {"planes": planes, "seen": seen}


def dump_events(events, path, max_events=4000):
    """Keep the head of every line, small enough for ``fixtures/``."""
    cut = {"planes": [{"name": p["name"], "lines": [
        {"name": l["name"], "events": l["events"][:max_events]}
        for l in p["lines"]]} for p in events["planes"]],
        "seen": events.get("seen", [])}
    with gzip.open(path, "wt") as f:
        json.dump(cut, f, separators=(",", ":"))


def load_events(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


def op_events(plane):
    """The events that are operations running on the device: the ``XLA Ops``
    line where the plane has one, else every line's events."""
    lines = [l for l in plane["lines"] if l["name"] == OPS_LINE] \
        or plane["lines"]
    evs = [[e[0][:NAME_CHARS], e[1], e[2]] for l in lines
           for e in l["events"] if e[2] > 0]
    evs.sort(key=lambda e: e[1])
    return evs


def union(evs):
    """Sorted events -> merged [start, end, first name, last name] spans."""
    spans = []
    for name, start, dur in evs:
        if spans and start <= spans[-1][1]:
            if start + dur > spans[-1][1]:
                spans[-1][1] = start + dur
                spans[-1][3] = name
        else:
            spans.append([start, start + dur, name, name])
    return spans


def reduce(events, top=10):
    """-> {"busy_s", "span_s", "device_planes", "n_ops", "device_ops",
    "idle_gaps"}; ``busy_s`` is None where no operation ran on a device."""
    busy, ops_total, gaps, n_ops, span = [], {}, [], 0, 0.0
    for plane in events["planes"]:
        evs = op_events(plane)
        if not evs:
            continue
        n_ops += len(evs)
        for name, _, dur in evs:
            ops_total[name] = ops_total.get(name, 0) + dur
        spans = union(evs)
        busy.append(sum(e - s for s, e, _, _ in spans) / 1e9)
        span = max(span, (spans[-1][1] - spans[0][0]) / 1e9)
        for a, b in zip(spans, spans[1:]):
            gaps.append((f"after {a[3]} before {b[2]}", (b[0] - a[1]) / 1e9))
    n_planes = len(busy)
    ops = sorted(ops_total.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return {
        "busy_s": sum(busy) / n_planes if busy else None,
        "span_s": span,
        "device_planes": n_planes,
        "n_ops": n_ops,
        "device_ops": [[n, d / 1e9 / max(n_planes, 1)] for n, d in ops],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
        "seen": events.get("seen", []),
    }
